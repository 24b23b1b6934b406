"""Read the initializers of an ONNX file, with nothing but the protobuf wire
format (protobuf encoding guide; onnx.proto3: ``ModelProto.graph`` = 7,
``GraphProto.initializer`` = 5, ``TensorProto`` dims 1, data_type 2,
float_data 4, int64_data 7, name 8, raw_data 9).

The reference reads the model file the way any consumer of the file would:
this shares no code with the program's ``synapseml_tpu/onnx/wire.py``, so a
fault in the program's parser shows as a disagreement, not as agreement.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

_DTYPES = {1: np.float32, 6: np.int32, 7: np.int64, 11: np.float64}


def _varint(buf: memoryview, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, pos
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message, nested messages and
    bytes as memoryviews (no copy of a 400 MB file)."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an ONNX file")
        yield field, wire, value


def _tensor(buf: memoryview) -> Tuple[str, "np.ndarray | None"]:
    dims, dtype, name, raw, floats, ints = [], 0, "", None, None, None
    for field, wire, value in _fields(buf):
        if field == 1 and wire == 0:
            dims.append(value)
        elif field == 1:  # packed dims
            pos = 0
            while pos < len(value):
                d, pos = _varint(value, pos)
                dims.append(d)
        elif field == 2:
            dtype = value
        elif field == 8:
            name = bytes(value).decode()
        elif field == 9:
            raw = value
        elif field == 4:
            floats = value
        elif field == 7 and wire == 2:
            ints = value
    np_dtype = _DTYPES.get(dtype)
    if np_dtype is None:
        return name, None
    if raw is not None:
        arr = np.frombuffer(raw, dtype=np.dtype(np_dtype).newbyteorder("<"))
    elif floats is not None:
        arr = np.frombuffer(floats, dtype="<f4")
    elif ints is not None:
        vals, pos = [], 0
        while pos < len(ints):
            v, pos = _varint(ints, pos)
            vals.append(v - (1 << 64) if v >> 63 else v)
        arr = np.asarray(vals, dtype=np_dtype)
    else:
        arr = np.zeros(int(np.prod(dims)) if dims else 0, dtype=np_dtype)
    return name, arr.reshape(dims)


def read_initializers(model_bytes: bytes) -> Dict[str, np.ndarray]:
    """name -> array for every initializer of a numeric type this reader
    knows; views into ``model_bytes`` (read-only), not copies."""
    out: Dict[str, np.ndarray] = {}
    for field, wire, graph in _fields(memoryview(model_bytes)):
        if field != 7 or wire != 2:
            continue
        for gfield, gwire, tensor in _fields(graph):
            if gfield == 5 and gwire == 2:
                name, arr = _tensor(tensor)
                if arr is not None:
                    out[name] = arr
    if not out:
        raise ValueError("no initializer found: not an ONNX model file")
    return out

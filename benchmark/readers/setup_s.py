"""Process start to the first timed call: open the chip, build the model
file, compile or load from the cache, warm the cell's shape, make the pool."""


def read(record: dict, params: dict):
    return record["setup_s"]

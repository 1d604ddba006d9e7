"""Process start to the window's opening: loading, weights, warm-up, the
traffic's own preload and lead-in, compilation in a run that compiles."""


def read(record: dict):
    return record["setup_s"]

"""Process start to the first timed request or frame: imports, compile or
compile-cache load, inputs, warm-up."""


def read(rec):
    return rec["setup_s"]

"""Process start to the first timed study: imports, build, compile (or
cache fetch) and the warm-up study (host clock)."""


def read(record):
    return record.setup_s

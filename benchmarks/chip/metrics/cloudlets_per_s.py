"""Cloudlets completed in the window's studies, summed over lanes, over
the window's wall time (first study's start to last study's end)."""


def read(record):
    return sum(s.cloudlets for s in record.studies) / record.window_s

"""Straggling between chips: per traced study, the busiest chip's program
time less the least busy chip's, over the busiest chip's, mean over the
traced studies (device trace).  Nothing to read on fewer than two chips."""


def read(record):
    if record.trace is None or len(record.trace.chips) < 2:
        return None
    spreads = [(max(chips) - min(chips)) / max(chips)
               for chips in record.trace.study_busy_s() if max(chips) > 0]
    if not spreads:
        return None
    return 100.0 * sum(spreads) / len(spreads)

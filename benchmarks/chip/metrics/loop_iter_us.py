"""Device time per trip of the program's run loop: per traced study, the
busiest chip's program time inside the study's span (device trace) over
the study's loop trips (the counter ``iterations``: the most of any
lane); the median over the traced studies."""
import statistics

COUNTER = "iterations"


def read(record):
    if record.trace is None or not record.trace.chips:
        return None
    per_study = record.trace.study_busy_s()
    trips = [s.counters.get(COUNTER) for s in record.studies[:len(per_study)]]
    if len(trips) < len(per_study) or not all(trips):
        return None
    return 1e6 * statistics.median(max(chips) / n
                                   for chips, n in zip(per_study, trips))

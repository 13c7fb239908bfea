"""Device time per traced study: seconds a chip executed a program
inside the study's span, the busiest chip, mean over traced studies."""


def read(record):
    if record.trace is None or not record.trace.chips:
        return None
    per_study = record.trace.study_busy_s()
    return 1e3 * sum(max(chips) for chips in per_study) / len(per_study)

"""Share of the traced window in which no program ran on the device,
mean over the chips the cell uses."""


def read(record):
    if record.trace is None or not record.trace.chips:
        return None
    window = record.trace.window_s()
    busy = record.trace.busy_s()
    return 100.0 * sum(1.0 - b / window for b in busy) / len(busy)

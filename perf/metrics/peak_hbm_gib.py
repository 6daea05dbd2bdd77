"""End to end: the device allocator's ``peak_bytes_in_use`` after the
window, on the fullest chip, in GiB: the largest grid that fits. None
where the runtime does not report it."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2**30

"""setup_s: process start to the start of the measured window (init,
calibration, FAQ quantization, engine build, warm-up, cache loads)."""


def read(run):
    return run.setup["setup_s"]

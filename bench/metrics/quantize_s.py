"""quantize_s: host clock around the seeded init, calibration and FAQ
int4 packing, ending when the packed tree is ready on the device."""


def read(run):
    return run.setup["quantize_s"]

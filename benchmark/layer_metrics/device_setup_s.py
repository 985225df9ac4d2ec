"""Set-up: the part of ``setup_s`` from the chip in hand to the window's
opening: the program's import, the model object, the seed's weights, the
warm-up, the ramp and, in a cold run, compilation: everything in
``setup_s`` that code of the repo can move."""


def read(run):
    before = run.get("pre_device_s")
    whole = (run.get("end_to_end") or {}).get("setup_s")
    if before is None or whole is None:
        return None
    return whole - before

"""Set-up: the part of ``setup_s`` before jax handed over the chip: the
interpreter's start, ``import jax`` and the TPU runtime's start. No code of
the repo runs in it, and on a one-chip machine's shared host it wandered
between 8.7 and 16.0 s from run to run (PERF.md, PR 24); read beside
``device_setup_s`` it says which part of ``setup_s`` a change moved."""


def read(run):
    return run.get("pre_device_s")

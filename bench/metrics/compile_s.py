"""compile_s: seconds of XLA compiles and compile-cache reads in set-up,
summed from JAX's backend-compile monitoring events (as chip_smoke.py
reads them).  Moves setup_s."""


def read(rec):
    return rec.compile_setup_s

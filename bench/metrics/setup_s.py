"""setup_s: process start to the first timed call (host clock): TPU
start-up, weights from the seed, phase 1, compile or cache load, warm-up."""


def read(rec):
    return rec["setup_s"]

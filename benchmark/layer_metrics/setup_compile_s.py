"""Seconds of set-up spent in the warm-up calls: the first call of each
shape the window uses, which compiles in a fresh checkout and loads from
the persistent cache afterwards."""


def read(facts):
    return facts["setup"].get("warmup")

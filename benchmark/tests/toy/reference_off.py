"""A stand-in reference that a test's configuration names: every served
token lies 1.0 below its best, so a run that used it reads incorrect."""


def gaps(params, sizes, prompt, served, *, control=False, **_) -> dict:
    import numpy as np

    return {"gaps": np.ones((len(served),), np.float32)}

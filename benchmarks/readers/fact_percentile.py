"""A percentile of a list the driver recorded (``args["fact"]``)."""

import numpy as np


def read(args, run):
    values = run["facts"].get(args["fact"])
    if values is None or not len(values):
        return None
    return float(np.percentile(np.asarray(values, float), args["q"]))

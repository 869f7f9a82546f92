"""Mean ALT rounds the engine applied per instance (`FleetResult.iters`):
a count, which tells less work apart from faster work."""
import numpy as np


def read(reading):
    iters = [r["iters"] for r in reading.ctx.records]
    return float(np.mean(np.concatenate(iters))) if iters else None

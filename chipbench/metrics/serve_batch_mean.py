"""Mean admission batch that the answered requests rode in."""

import numpy as np


def read(run):
    sizes = [r.batch_size for r in run.driver.responses
             if r is not None and r.ok]
    return float(np.mean(sizes)) if sizes else None

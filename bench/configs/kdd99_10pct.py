"""KDD99-10% data: the seeded twin of the paper's own dataset.

494,021 connection records x 41 columns (3 categorical at indices 1-3,
with the real vocabularies), 5 superclasses at the real subset's
marginals.  Features are class-conditional, with heavy-tailed traffic
volumes, so a tree beats the base rate by a wide margin but not
trivially.  The generator is a copy of the one the program ships, kept
here so that the benchmark's data cannot change with the program.

``draw`` gives a run its rows: the configuration's one fixed table
(``data_seed``), in an order drawn from the run's seed.  Every seed so
grows the same tree and prices the same grid; only the row order moves.
"""
from __future__ import annotations

import numpy as np

PRIORS = (0.1969, 0.7924, 0.0083, 0.0023, 0.0001)
CAT_COLS = (1, 2, 3)
N_FEATURES = 41
PROTOCOLS = ("tcp", "udp", "icmp")
SERVICES = ("http", "smtp", "ftp", "ftp_data", "telnet", "pop_3",
            "domain_u", "private", "ecr_i", "eco_i", "finger", "other")
FLAGS = ("SF", "S0", "REJ", "RSTR", "RSTO", "SH")


def synth(m: int, seed: int):
    """``(cols, y)``: 41 raw columns (numeric arrays, or lists of strings
    for the categorical ones) and int32 superclass ids."""
    rng = np.random.default_rng(seed)
    n_cls = len(PRIORS)
    counts = np.maximum(np.round(np.asarray(PRIORS) * m).astype(int), 8)
    counts[np.argmax(counts)] += m - counts.sum()
    y = np.repeat(np.arange(n_cls, dtype=np.int32), counts)
    y = y[rng.permutation(m)]

    p_proto = np.array([[.75, .20, .05], [.30, .05, .65], [.45, .15, .40],
                        [.90, .08, .02], [.95, .04, .01]])
    p_flag = np.array([[.90, .02, .04, .02, .01, .01],
                       [.55, .35, .05, .03, .01, .01],
                       [.25, .30, .25, .10, .05, .05],
                       [.70, .05, .15, .05, .04, .01],
                       [.85, .03, .05, .03, .02, .02]])
    p_service = np.array(
        [[.40, .12, .06, .08, .03, .05, .10, .05, .01, .01, .04, .05],
         [.05, .01, .01, .01, .01, .01, .02, .30, .50, .05, .01, .02],
         [.05, .02, .02, .02, .02, .02, .05, .35, .10, .25, .05, .05],
         [.05, .05, .25, .20, .25, .05, .02, .05, .01, .01, .05, .01],
         [.05, .02, .10, .05, .55, .02, .02, .05, .01, .01, .10, .02]])

    def draw(vocab, probs):
        out = np.empty(m, dtype=object)
        for c in range(n_cls):
            sel = y == c
            out[sel] = np.asarray(vocab, dtype=object)[
                rng.choice(len(vocab), size=int(sel.sum()), p=probs[c])]
        return out

    cats = {1: draw(PROTOCOLS, p_proto), 2: draw(SERVICES, p_service),
            3: draw(FLAGS, p_flag)}
    n_num = N_FEATURES - len(CAT_COLS)
    # per-class numeric signatures, fixed whatever the seed
    sig_rng = np.random.default_rng(1999)
    shift = np.where(sig_rng.uniform(size=(n_cls, n_num)) < .35,
                     sig_rng.normal(scale=2.0, size=(n_cls, n_num)), 0.0)
    num = rng.normal(size=(m, n_num)).astype(np.float32) + \
        shift[y].astype(np.float32)
    num[:, 1] = np.exp(rng.normal(size=m) * 2.0
                       + np.asarray([5., 8., 2., 6., 4.])[y]).astype(
                           np.float32)
    num[:, 2] = np.exp(rng.normal(size=m) * 2.0
                       + np.asarray([6., 1., 1., 5., 5.])[y]).astype(
                           np.float32)
    cols, ni = [], 0
    for j in range(N_FEATURES):
        if j in CAT_COLS:
            cols.append(list(cats[j]))
        else:
            cols.append(num[:, ni])
            ni += 1
    return cols, y


def draw(conf: dict, m: int, seed: int, offset: int = 0):
    """``m`` rows of the fixed table ``synth(m, data_seed + offset)``,
    permuted by ``seed``: ``(cols, y)`` as ``synth`` gives them."""
    cols, y = synth(m, conf["data_seed"] + offset)
    perm = np.random.default_rng(seed).permutation(m)
    return ([c[perm] if isinstance(c, np.ndarray)
             else list(np.asarray(c, dtype=object)[perm]) for c in cols],
            y[perm])

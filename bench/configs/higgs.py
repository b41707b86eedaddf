"""HIGGS data at the UCI dataset's schema, made from a seed.

28 dense float columns: 21 kinematic ones (lepton pT, eta, phi; missing
energy and its phi; four jets with pT, eta, phi and a b-tag that takes
three values) and 7 derived invariant masses (m_jj, m_jjj, m_lv, m_jlv,
m_bb, m_wbb, m_wwbb) computed from them.  Signal events (about 53%) carry
a resonance in m_bb, m_wbb and m_wwbb and more b-tags, so the label is
learnable from both the raw and the derived columns, as in the real set.
The binary label is float32 0/1.

``bin_rows`` bins the full table with the edges that ``fit_bins`` found
on a sample: ``fit_bins`` parses every value in Python, which takes
minutes at 10.5M x 28.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8

BTAG = np.array([0.0, 1.1074, 2.2149])


BLOCK = 1 << 20          # rows per generator block


def synth(m: int, seed: int, signal_share: float = 0.53):
    """``(x [m, 28] float32, y [m] float32)``.  Rows are made in blocks
    of ``BLOCK``, each from its own child of the seed, on a few threads;
    the same seed gives the same rows whatever the thread count."""
    blocks = [(a, min(a + BLOCK, m)) for a in range(0, m, BLOCK)]
    seqs = np.random.SeedSequence(seed).spawn(len(blocks))
    x = np.empty((m, 28), dtype=np.float32)
    y = np.empty(m, dtype=np.float32)

    def fill(i):
        a, b = blocks[i]
        x[a:b], y[a:b] = _block(np.random.default_rng(seqs[i]), b - a,
                                signal_share)
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(len(blocks))))
    return x, y


def _block(rng, m, signal_share):
    f32 = np.float32

    def normal(mu, sd):
        return rng.standard_normal(m, dtype=f32) * f32(sd) + mu

    def uniform(lo, hi):
        return rng.random(m, dtype=f32) * f32(hi - lo) + f32(lo)

    y = rng.random(m, dtype=f32) < f32(signal_share)
    s = y.astype(f32)
    lep = (np.exp(normal(-0.1, 0.55)), np.clip(normal(0, 1), -2.43, 2.43),
           uniform(-1.74, 1.74))
    met = np.exp(normal(-0.15 + 0.05 * s, 0.6))
    met_phi = uniform(-1.74, 1.74)
    cols = list(lep) + [met, met_phi]
    jets = []
    cut_b = np.where(y[:, None], f32([0.45, 0.60]), f32([0.60, 0.75]))
    for j in range(4):
        pt = np.exp(normal(-0.05 - 0.12 * j + 0.06 * s, 0.5))
        eta = np.clip(normal(0, 1.1), -2.73, 2.73)
        phi = uniform(-1.74, 1.74)
        u = rng.random(m, dtype=f32)
        tag = BTAG[(u[:, None] > cut_b).sum(1)].astype(f32)
        jets.append((pt, eta, phi))
        cols += [pt, eta, phi, tag]

    def mass(*parts):
        # massless four-vectors from (pT, eta, phi); phi scaled to radians
        e = px = py = pz = f32(0)
        for pt, eta, phi in parts:
            a = phi * f32(np.pi / 1.74)
            px = px + pt * np.cos(a)
            py = py + pt * np.sin(a)
            pz = pz + pt * np.sinh(eta)
            e = e + pt * np.cosh(eta)
        return np.sqrt(np.maximum(e * e - px * px - py * py - pz * pz, 0))

    nu = (met, np.zeros(m, dtype=f32), met_phi)
    j1, j2, j3, j4 = jets
    res = normal(1.0, 0.08)                    # the signal's resonance
    m_lv = mass(lep, nu)
    m_jlv = mass(j1, lep, nu)
    m_bb = np.where(y, res * (1 + f32(0.1) * normal(0, 1)), mass(j3, j4))
    m_wbb = np.where(y, f32(1.3) * res + f32(0.1) * m_lv, mass(j2, j3, j4))
    m_wwbb = np.where(y, f32(1.6) * res + f32(0.15) * m_jlv,
                      mass(j1, j2, j3, j4))
    cols += [mass(j1, j2), mass(j1, j2, j3), m_lv, m_jlv, m_bb, m_wbb,
             m_wwbb]
    return np.stack(cols, axis=1).astype(f32), s


def bin_rows(x, edges, n_num, missing):
    """Bin ids [m, k] int32 of float32 columns ``x``, as the program's
    ``transform`` gives them for numeric values: the first edge at or
    above the value, clamped to the last numeric bin, and the missing
    bin for NaN or for a column with no numeric bins.  The edges are
    values of the float32 columns, so comparing in float32 is exact."""
    m, k = x.shape
    out = np.empty((m, k), dtype=np.int32)

    def one(j):
        if n_num[j] == 0:
            out[:, j] = missing[j]
            return
        col = np.ascontiguousarray(x[:, j])
        idx = np.searchsorted(np.asarray(edges[j], dtype=np.float32), col,
                              side="left")
        out[:, j] = np.where(np.isnan(col), missing[j],
                             np.minimum(idx, n_num[j] - 1))
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(one, range(k)))
    return out

"""The plain reference that decides ``correct``: numpy, float64, no
import of the program.

A fitted tree is an answer that can be checked node by node.  Given the
binned table and each row's statistics, the reference routes every row
down the program's tree by the paper's comparison rules, sums each
node's statistics itself, and asks of every node what the program's
guarantees say:

* its row count (classification) or its Newton value -G/H (boosting) is
  the one its rows give;
* it is a leaf exactly when the stopping rules make it one;
* its split is the best split of its rows: the reference scores every
  candidate of every feature and reads how far the program's chosen
  candidate falls below the best one.  A float near-tie may go either
  way, so this is a gap with a limit and not an equality.

The same functions grow a tree from scratch (``grow``), which is how the
control runs: the reference in the program's place, with its histograms
rounded to a lower precision.

Tree arrays are the program's layout: node ids level-contiguous, children
allocated in sibling pairs, fields ``feat op tbin label count depth left
right leaf``.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8

OP_LE, OP_GT, OP_EQ = 0, 1, 2
FIELDS = ("feat", "op", "tbin", "label", "count", "depth", "left", "right",
          "leaf")


def host_tree(tree) -> dict:
    """The program's tree as host arrays cut to its node count."""
    n = int(tree.n_nodes)
    return {f: np.asarray(getattr(tree, f))[:n] for f in FIELDS}


def go_left(xbin, n_num_f, op, tbin):
    """The paper's comparison rules on bin ids: numeric predicates are
    false on categorical and missing bins; equality holds on one id."""
    numeric = xbin < n_num_f
    return np.where(op == OP_LE, numeric & (xbin <= tbin),
                    np.where(op == OP_GT, numeric & (xbin > tbin),
                             xbin == tbin))


class Rows:
    """The binned rows as the reference reads them: one contiguous column
    per feature in the narrowest unsigned integer that holds the bins."""

    def __init__(self, bins, n_bins):
        bins = np.asarray(bins)
        dt = np.uint8 if n_bins <= 256 else np.uint16
        self.m, self.k = bins.shape
        self.cols = np.ascontiguousarray(bins.T.astype(dt))
        self.n_bins = n_bins

    def at(self, f):
        """Each row's bin of feature ``f[row]``."""
        idx = f.astype(np.int64) * self.m + np.arange(self.m)
        return self.cols.reshape(-1)[idx].astype(np.int64)


def route(rows, n_num, tree):
    """Per depth d (1-based), the node id of every row at depth d (a row
    that reached a leaf earlier stays there).  Returns a list indexed by
    depth - 1."""
    node = np.zeros(rows.m, dtype=np.int64)
    out = [node]
    internal = (~tree["leaf"]) & (tree["left"] >= 0)
    for _ in range(int(tree["depth"].max()) - 1):
        go = internal[node]
        f = np.where(go, tree["feat"][node], 0)
        left = go_left(rows.at(f), n_num[f], tree["op"][node],
                       tree["tbin"][node])
        node = np.where(go, np.where(left, tree["left"][node],
                                     tree["right"][node]), node)
        out.append(node)
    return out


def node_sums(node_at_depth, depth, n_nodes, stats):
    """[n_nodes, C] float64 sums of ``stats`` [m, C] over each node's rows."""
    out = np.zeros((n_nodes, stats.shape[1]))
    for d, node in enumerate(node_at_depth, start=1):
        at = np.flatnonzero(depth == d)
        if at.size == 0:
            continue
        for c in range(stats.shape[1]):
            s = np.bincount(node, weights=stats[:, c], minlength=n_nodes)
            out[at, c] = s[at]
    return out


def histograms(rows, stats, slot, width, rounding=None):
    """[width, K, B, C] float64 sums of ``stats`` over the rows of each
    slot (slot -1 rows are left out)."""
    keep = np.flatnonzero(slot >= 0)
    st = stats[keep]
    base = slot[keep].astype(np.int64) * rows.n_bins
    b = rows.n_bins
    out = np.zeros((width, rows.k, b, st.shape[1]))

    def one(j):
        idx = base + rows.cols[j][keep]
        for c in range(st.shape[1]):
            out[:, j, :, c] = np.bincount(
                idx, weights=st[:, c], minlength=width * b).reshape(width, b)
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(one, range(rows.k)))
    return out if rounding is None else rounding(out)


# ---------------------------------------------------------------------------
# scores: every candidate of every feature
# ---------------------------------------------------------------------------

def _exact(x):
    return x


def _log(x):
    return np.log(np.where(x > 0, x, 1.0))


def info_gain(pos, neg, r=_exact):
    """-H(T | split) per row (the paper's simplified information gain).
    ``r`` rounds every intermediate (the control's lower precision)."""
    tp = r(pos.sum(-1, keepdims=True))
    tn = r(neg.sum(-1, keepdims=True))
    tot = r(np.where(tp + tn > 0, tp + tn, 1.0))
    a = r(np.where(pos > 0, r(pos * r(r(_log(pos)) - r(_log(tp)))),
                   0.0).sum(-1))
    b = r(np.where(neg > 0, r(neg * r(r(_log(neg)) - r(_log(tn)))),
                   0.0).sum(-1))
    return r(r(a + b) / tot[..., 0])


def newton_gain(pos, neg, r=_exact):
    """G^2/H of both sides; channels (H, -G, ...) as the boosting rows
    give them.  ``r`` as for ``info_gain``."""
    hp, gp = pos[..., 0], pos[..., 1]
    hn, gn = neg[..., 0], neg[..., 1]
    return r(r(r(gp * gp) / np.where(hp > 0, hp, 1.0))
             + r(r(gn * gn) / np.where(hn > 0, hn, 1.0)))


class Rules:
    """A job's guarantees: its score, what counts as a side's weight, the
    stopping rules and the node value."""

    def __init__(self, kind: str, max_depth: int, min_samples_split: int = 2,
                 min_samples_leaf: int = 1):
        if kind not in ("classification", "newton"):
            raise ValueError(f"unknown tree kind {kind!r}")
        self.kind = kind
        self.max_depth = max_depth
        self.min_split = min_samples_split
        self.min_leaf = min_samples_leaf

    def score(self, pos, neg, r=_exact):
        return (info_gain if self.kind == "classification"
                else newton_gain)(pos, neg, r)

    def weight(self, s):
        return s.sum(-1) if self.kind == "classification" else s[..., 0]

    def value(self, s):
        """The node's label: majority class, or the Newton step -G/H."""
        if self.kind == "classification":
            return np.argmax(s, axis=-1).astype(np.float64)
        return s[..., 1] / np.where(s[..., 0] > 0, s[..., 0], 1.0)

    def count(self, s):
        return np.round(self.weight(s))

    def pure(self, s):
        if self.kind == "classification":
            return s.max(-1) == s.sum(-1)
        h = np.where(s[..., 0] > 0, s[..., 0], 1.0)
        return s[..., 2] - s[..., 1] ** 2 / h <= 1e-10 * np.maximum(
            s[..., 0], 1.0)


def candidates(hist, n_num, n_cat, rules, r=_exact):
    """Scores [3, K, B] (op-major: <=, >, =) of every candidate split of
    one node's histogram [K, B, C], -inf where a candidate is invalid,
    and the node's own no-split score.  ``r`` rounds the arithmetic of
    the scores (the control's lower precision).  A side is valid when
    its weight is at least ``min_samples_leaf``."""
    k, b, _ = hist.shape
    ids = np.arange(b)
    is_num = ids[None, :] < n_num[:, None]
    is_cat = (ids[None, :] >= n_num[:, None]) & (
        ids[None, :] < (n_num + n_cat)[:, None])
    tot = hist.sum(axis=1, keepdims=True)
    prefix = np.cumsum(hist * is_num[:, :, None], axis=1)
    pos = np.stack([prefix, prefix[:, -1:] - prefix, hist])
    neg = tot[None] - pos
    node_total = tot[0, 0]
    ok = (np.stack([is_num, is_num, is_cat])
          & (rules.weight(pos) >= rules.min_leaf)
          & (rules.weight(neg) >= rules.min_leaf))
    score = np.where(ok, rules.score(pos, neg, r), -np.inf)
    base = rules.score(node_total[None], np.zeros_like(node_total)[None])[0]
    return score, base


def side_weights(hist, n_num, pick, rules):
    """The weights of the two sides of candidate ``pick`` (op, feature,
    bin) of one node's histogram [K, B, C]."""
    op, f, b = (int(v) for v in pick)
    h = hist[f]
    ids = np.arange(h.shape[0])
    numeric = ids < n_num[f]
    left = (numeric & (ids <= b) if op == OP_LE else
            numeric & (ids > b) if op == OP_GT else ids == b)
    pos = h[left].sum(0)
    return float(rules.weight(pos)), float(rules.weight(h.sum(0) - pos))


def best(score):
    """(op, feat, bin) of the best candidate; ties go to the first in
    op-major order, as the paper's flat argmax does."""
    flat = int(np.argmax(score.reshape(-1)))
    return np.unravel_index(flat, score.shape)


# ---------------------------------------------------------------------------
# checking a program tree
# ---------------------------------------------------------------------------

class TreeCheck:
    """What one tree's check read.  ``nodes_off`` counts nodes whose
    count (classification), label or leaf decision differs from the
    reference's; ``value_gaps`` holds each node's |label - reference| (the
    Newton value), ``split_gaps`` each checked split's shortfall below the
    best candidate (the scale: the larger of the best score and the
    node's unsplit score), both as raw numbers with the scale to divide
    by."""

    def __init__(self):
        self.nodes_off = 0
        self.value_gaps: list = []      # (gap, reference value)
        self.split_gaps: list = []      # (shortfall, score scale)
        self.row_counts = None
        self.final_nodes = None
        self.notes: list = []           # why each node is off

    def split_gap(self) -> float:
        return scaled_max(self.split_gaps)


def scaled_max(pairs) -> float:
    """max gap / max(its own scale, the median scale): a gap on a node
    whose own value is all but zero is read against a typical node."""
    if not pairs:
        return 0.0
    gap = np.array([p[0] for p in pairs], dtype=np.float64)
    scale = np.abs(np.array([p[1] for p in pairs], dtype=np.float64))
    floor = max(float(np.median(scale)), 1e-30)
    return float(np.max(gap / np.maximum(scale, floor)))


def check_tree(tree, rows, stats, n_num, n_cat, rules,
               split_nodes=None) -> TreeCheck:
    """Check ``tree`` (host arrays) against the reference on ``rows``
    (``Rows``) with statistics ``stats`` [m, C] (class one-hot, or boosting's
    (h, -g, g^2/h)).  ``split_nodes``: the internal nodes whose split is
    scored against every candidate (all of them when None)."""
    chk = TreeCheck()
    n = tree["feat"].shape[0]
    depth = tree["depth"]
    levels = route(rows, n_num, tree)
    sums = node_sums(levels, depth, n, stats)
    chk.row_counts = node_sums(levels, depth, n,
                               np.ones((rows.m, 1)))[:, 0]
    chk.final_nodes = levels[-1]
    internal = (~tree["leaf"]) & (tree["left"] >= 0)
    if split_nodes is None:
        split_nodes = np.flatnonzero(internal)
    split_nodes = set(int(u) for u in split_nodes)

    value = rules.value(sums)
    off = np.zeros(n, dtype=bool)
    if rules.kind == "classification":
        off |= tree["count"] != chk.row_counts
        off |= tree["label"] != value
    else:
        chk.value_gaps = list(zip(np.abs(tree["label"] - value), value))

    pure = rules.pure(sums)
    must_stop = (depth >= rules.max_depth) | (
        rules.count(sums) < rules.min_split)
    if rules.kind == "classification":
        must_stop |= pure
    # a leaf that no rule stops must have no valid split; an internal
    # node must have none of the stopping rules and a valid chosen split.
    # Zero variance of a Newton target is below float32's resolution in
    # the program's test, so such a node may go either way.
    open_leaf = ~internal & ~must_stop & ~pure
    off |= internal & must_stop
    for d in range(1, int(depth.max()) + 1):
        at = np.flatnonzero(depth == d)
        want = [u for u in at if u in split_nodes or open_leaf[u]]
        if not want:
            continue
        slot_of = np.full(n, -1, dtype=np.int64)
        slot_of[want] = np.arange(len(want))
        hist = histograms(rows, stats, slot_of[levels[d - 1]], len(want))
        for i, u in enumerate(want):
            score, base = candidates(hist[i], n_num, n_cat, rules)
            top = score.max()
            if not internal[u]:
                if np.isfinite(top):
                    off[u] = True
                    chk.notes.append(f"node {u}: a leaf with a valid split")
                continue
            pick = tree["op"][u], tree["feat"][u], tree["tbin"][u]
            chosen = score[pick]
            if np.isfinite(chosen):
                chk.split_gaps.append((max(top - chosen, 0.0),
                                       max(abs(top), abs(base))))
            else:
                off[u] = True
                w = side_weights(hist[i], n_num, pick, rules)
                chk.notes.append(
                    f"node {u}: chosen split invalid: side weights "
                    f"{w[0]!r}, {w[1]!r} against the floor {rules.min_leaf!r}")
    for u in np.flatnonzero(internal & must_stop):
        chk.notes.append(f"node {u}: split where a stopping rule holds")
    if rules.kind == "classification":
        for u in np.flatnonzero(tree["count"] != chk.row_counts):
            chk.notes.append(f"node {u}: count {tree['count'][u]} against "
                             f"{chk.row_counts[u]:.0f}")
    chk.nodes_off = int(off.sum())
    return chk


# ---------------------------------------------------------------------------
# binning: the table the program must have made from the raw columns
# ---------------------------------------------------------------------------

def numeric_edges(vals, max_num_bins):
    """Right-inclusive upper edges of a numeric column's bins: every
    distinct value when there are at most ``max_num_bins``, else the
    values nearest ``max_num_bins`` evenly spaced quantiles of the rows,
    with the largest value kept."""
    uniq = np.unique(vals)
    if uniq.size <= max_num_bins:
        return uniq
    q = np.unique(np.quantile(vals, np.linspace(0.0, 1.0, max_num_bins),
                              method="nearest"))
    return q if q[-1] >= uniq[-1] else np.append(q, uniq[-1])


def _parse(col):
    """(float64 values, NaN where not numeric; category value per row or
    None) of a float column, or of a column of strings: a string that
    reads as a number is a number, any other is a category."""
    if isinstance(col, np.ndarray) and col.dtype.kind == "f":
        return col.astype(np.float64), None
    uniq, inv = np.unique(np.asarray(col, dtype=object), return_inverse=True)
    num = np.full(uniq.size, np.nan)
    for i, v in enumerate(uniq):
        try:
            num[i] = float(v)
        except (TypeError, ValueError):
            pass
    cat = np.where(np.isnan(num), uniq, None)
    return num[inv], cat[inv]


def column_layout(col, max_num_bins):
    """(numeric edges, {category: id}) of one raw column: categories are
    numbered in order of first appearance."""
    num, cat = _parse(col)
    vals = num[~np.isnan(num)]
    edges = (numeric_edges(vals, max_num_bins) if vals.size
             else np.zeros(0))
    cats: dict = {}
    if cat is not None:
        for v in cat[np.isnan(num)]:
            if v is not None and v not in cats:
                cats[v] = len(cats)
    return edges, cats


def column_bins(col, layout):
    """Bin ids of a raw column under ``layout``: numeric bins (the first
    edge at or above the value, clamped to the last), then categories,
    then the missing bin for NaN, unseen categories and numbers in a
    column with no numeric bins."""
    edges, cats = layout
    num, cat = _parse(col)
    n_num = edges.size
    out = np.full(num.size, n_num + len(cats), dtype=np.int64)
    isnum = ~np.isnan(num)
    if n_num:
        out[isnum] = np.minimum(np.searchsorted(edges, num[isnum]),
                                n_num - 1)
    if cat is not None:
        for v, i in cats.items():
            out[cat == v] = n_num + i
    return out


def table_off(bins, n_num, n_cat, cols, layouts, edges=None):
    """How far the program's binned ``bins`` [m, K] (with its per-feature
    bin counts, and its numeric ``edges`` when given) is from the
    reference's binning of the raw ``cols``: the entries that differ, plus
    one for each feature whose counts or edges differ."""
    off = 0
    for j, (col, lay) in enumerate(zip(cols, layouts)):
        off += int(n_num[j] != lay[0].size) + int(n_cat[j] != len(lay[1]))
        if edges is not None:
            off += int(not np.array_equal(np.asarray(edges[j],
                                                     dtype=np.float64),
                                          lay[0]))
        off += int(np.count_nonzero(np.asarray(bins[:, j])
                                    != column_bins(col, lay)))
    return off


# ---------------------------------------------------------------------------
# growing a tree (the control: the reference in the program's place)
# ---------------------------------------------------------------------------

def grow(rows, stats, n_num, n_cat, rules, rounding=None) -> dict:
    """A greedy level-synchronous tree in the program's layout.  With
    ``rounding`` the per-level histograms and every step of the
    candidates' score arithmetic are rounded (the control's lower
    precision)."""
    m = rows.m
    cap = 2 * m + 1
    t = {f: np.full(cap, -1, dtype=np.int64) for f in
         ("feat", "op", "tbin", "left", "right")}
    t["label"] = np.zeros(cap)
    t["count"] = np.zeros(cap, dtype=np.int64)
    t["depth"] = np.zeros(cap, dtype=np.int64)
    t["leaf"] = np.zeros(cap, dtype=bool)
    node = np.zeros(m, dtype=np.int64)
    start, end, nxt, d = 0, 1, 1, 1
    while start < end:
        width = end - start
        slot = node - start
        slot = np.where((slot >= 0) & (slot < width), slot, -1)
        hist = histograms(rows, stats, slot, width, rounding)
        for i in range(width):
            u = start + i
            s = hist[i, 0].sum(axis=0)
            t["depth"][u] = d
            t["label"][u] = rules.value(s)
            t["count"][u] = rules.count(s)
            score, _ = candidates(hist[i], n_num, n_cat, rules,
                                  rounding or _exact)
            stop = (d >= rules.max_depth or bool(rules.pure(s))
                    or rules.count(s) < rules.min_split
                    or not np.isfinite(score.max()))
            if stop:
                t["leaf"][u] = True
                continue
            op, f, b = best(score)
            t["op"][u], t["feat"][u], t["tbin"][u] = op, f, b
            t["left"][u], t["right"][u] = nxt, nxt + 1
            nxt += 2
        at = (node >= start) & (node < end)
        go = at & ~t["leaf"][node]
        f = np.where(go, t["feat"][node], 0)
        left = go_left(rows.at(f), n_num[f], t["op"][node],
                       t["tbin"][node])
        node = np.where(go, np.where(left, t["left"][node],
                                     t["right"][node]), node)
        start, end, d = end, nxt, d + 1
    return {f: a[:nxt] for f, a in t.items()}


def bf16(x):
    """Round to bfloat16 and back: the control's lower precision."""
    import ml_dtypes
    return np.asarray(x, dtype=np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


# ---------------------------------------------------------------------------
# Training-Only-Once Tuning: the grid by pruning one full tree
# ---------------------------------------------------------------------------

def toot_counts(tree, vrows, vy, n_num, dmax_values, smin_values,
                accumulate=None):
    """[Nd, Ns] correct predictions of the full tree pruned at each
    (max_depth, min_samples_split): a row stops at the first node on its
    path that is a leaf, holds fewer than ``smin`` rows, or lies at depth
    ``dmax``, and takes that node's label."""
    path = np.stack(route(vrows, n_num, tree), axis=1)        # [m, T]
    lab = tree["label"][path]
    cnt = tree["count"][path]
    internal = (~tree["leaf"]) & (tree["left"] >= 0)
    go = internal[path]
    m, t_len = path.shape
    out = np.zeros((len(dmax_values), len(smin_values)), dtype=np.float64)
    for j, smin in enumerate(smin_values):
        blocked = ~go | (cnt < smin)
        first = np.where(blocked.any(1), blocked.argmax(1), t_len - 1)
        for i, dmax in enumerate(dmax_values):
            stop = np.minimum(first, dmax - 1)
            ok = lab[np.arange(m), stop] == vy
            out[i, j] = ok.sum() if accumulate is None else accumulate(ok)
    return out


def pruned_nodes(tree, dmax, smin) -> int:
    """Node count of the full tree pruned at (dmax, smin)."""
    n, stack = 0, [0]
    while stack:
        u = stack.pop()
        n += 1
        if (tree["leaf"][u] or tree["left"][u] < 0 or tree["count"][u] < smin
                or tree["depth"][u] >= dmax):
            continue
        stack += [int(tree["left"][u]), int(tree["right"][u])]
    return n


def toot_best(counts, tree, dmax_values, smin_values):
    """The chosen cell: the highest count, then the fewest pruned nodes,
    then the first in grid order."""
    top = counts.max()
    best_cell, best_nodes = None, None
    for i, j in zip(*np.nonzero(counts == top)):
        nodes = pruned_nodes(tree, dmax_values[i], smin_values[j])
        if best_nodes is None or nodes < best_nodes:
            best_cell, best_nodes = (int(i), int(j)), nodes
    return best_cell

"""Record ``v5e_scopes.xplane.pb.gz``: one small UDT fit on a TPU chip,
traced inside the benchmark's ``bench.window`` span.

    python3 tests/bench/data/record_v5e_scopes.py <out_dir> [rows] [max_depth]

The fit is the ``kdd99_10pct`` twin drawn at ``rows`` rows (20,000), under
the ``udt_fit`` mix (``max_depth`` at most the mix's), on the default
``segment``/``jnp`` backends, after one untraced fit that compiles every
shape.  The profiler runs with the Python tracer off and the host tracer
at level 1, so that the host planes keep the program's spans and the
runtime's own events.  The TPU profiler writes each executed module's
HLO proto into a ``/host:metadata`` plane whatever ``enable_hlo_proto``
says; that plane, which no reader uses, is left out of the written file
so that it stays small, and the rest is compressed.  Every other plane is
kept byte for byte.  The file is ``<out_dir>/v5e_scopes.xplane.pb.gz``;
the profiler's own output stays under ``<out_dir>/plugins/profile/``.
"""
import dataclasses
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

HLO_PLANE = "/host:metadata"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def without_hlo_plane(buf: bytes) -> bytes:
    """The ``XSpace`` in ``buf`` without its HLO-proto plane (every field
    of an ``XSpace`` is length-delimited)."""
    from bench import xplane
    out = bytearray()
    for field, (lo, hi) in xplane._fields(buf, 0, len(buf)):
        if field == 1 and xplane._plane(buf, (lo, hi))[0] == HLO_PLANE:
            continue
        out += _varint(field << 3 | 2) + _varint(hi - lo) + buf[lo:hi]
    return bytes(out)


def main(out_dir: str, rows: int = 20_000, max_depth: int = 64) -> None:
    import jax
    from bench import trace
    from bench.configs import kdd99_10pct
    from bench.harness import WINDOW_SPAN
    from repro.core import TreeConfig, build_tree, fit_bins, obs
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_v5e_scopes: needs a TPU")
    with open(ROOT / "bench" / "configs" / "kdd99_10pct.json") as f:
        conf = json.load(f)
    mix = json.loads((ROOT / "bench" / "mixes" / "udt_fit.json").read_text())
    cols, y = kdd99_10pct.draw(conf, rows, seed=1)
    table = fit_bins(cols, max_num_bins=conf["max_num_bins"])
    table = dataclasses.replace(table, bins=jax.device_put(table.bins))
    cfg = TreeConfig(max_depth=min(max_depth, mix["max_depth"]),
                     min_samples_split=mix["min_samples_split"])
    jax.block_until_ready(build_tree(table, y, cfg, n_classes=5))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    before = obs.counters()
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        tree = build_tree(table, y, cfg, n_classes=5)
        jax.block_until_ready(tree)
    jax.profiler.stop_trace()
    after = obs.counters()
    raw = Path(trace.find_xplane(out_dir)).read_bytes()
    small = without_hlo_plane(raw)
    (Path(out_dir) / "v5e_scopes.xplane.pb.gz").write_bytes(
        gzip.compress(small, mtime=0))
    print(json.dumps({"rows": rows, "max_depth": cfg.max_depth,
                      "nodes": int(tree.n_nodes),
                      "depth": tree.max_tree_depth,
                      "raw_bytes": len(raw), "bytes": len(small),
                      **{k: after[k] - before[k] for k in after}}))


if __name__ == "__main__":
    main(sys.argv[1], *map(int, sys.argv[2:]))

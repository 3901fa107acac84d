"""Profile the fresh-content host walk (bench.py's
exact_fresh_content_host_walk metric) in isolation: device outputs are
whatever the CPU backend produces; only host_confirm_seconds matters.

Floor gate (preflight): ``--check-floor`` measures the BATCHED walk
(docs/HOST_WALK.md) on the bundled corpus plus the walk-stress
templates and fails when the rows/s rate drops below the recorded
floor in ``tools/walk_floor.json`` by more than ``SWARM_FLOOR_FACTOR``
(default 2x slack — walk rates are host-noise-sensitive). Record a new
floor with ``--record-floor`` after an intentional change; set
``SWARM_FLOOR_SKIP=1`` to bypass on known-noisy hosts. The floor is
keyed to the measuring configuration (rows, corpus size, core count) —
a mismatch skips rather than fails.

Usage: python tools/profile_walk.py [--rows 3072] [--iters 8]
       [--cprofile] [--ab] [--record-floor | --check-floor]
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FLOOR_PATH = Path(__file__).parent / "walk_floor.json"
DEFAULT_CORPUS = "/root/reference/worker/artifacts/templates"


def _measure_floor_rate(rows: int, iters: int):
    """Batched-walk rows/s on the bundled corpus + walk-stress
    templates (the confirm-heavy feed the walk A/B uses) — best of 3
    rounds, fresh content every round."""
    from bench import walk_stress_rows, walk_stress_templates
    from swarm_tpu.fingerprints import load_corpus
    from swarm_tpu.ops.engine import MatchEngine

    corpus = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "data", "templates",
    )
    templates, _errors = load_corpus(corpus)
    templates = list(templates) + walk_stress_templates()
    eng = MatchEngine(
        templates, mesh=None, batch_rows=rows, max_body=2048,
        max_header=512,
    )
    batches = [walk_stress_rows(rows, seed=7000 + i) for i in range(iters)]
    eng.match_packed(batches[0])  # warm jit shapes
    s = eng.stats
    best = 0.0
    for _round in range(3):
        eng.clear_content_memos()
        h0 = s.host_confirm_seconds
        for b in batches:
            eng.match_packed(b)
        walk = s.host_confirm_seconds - h0
        rate = rows * iters / walk if walk > 0 else 0.0
        best = max(best, rate)
    return best, len(templates), eng.walk_threads


def run_floor(argv) -> int:
    rows, iters = 256, 2
    rate, n_templates, threads = _measure_floor_rate(rows, iters)
    config = {
        "rows": rows,
        "iters": iters,
        "corpus_templates": n_templates,
        "cpus": os.cpu_count() or 1,
    }
    print(
        f"batched walk: {rate:.0f} rows/s ({threads} walk threads, "
        f"{n_templates} templates)",
        file=sys.stderr,
    )
    if "--record-floor" in argv:
        rec = {"walk_rows_per_sec": round(rate, 1), **config}
        FLOOR_PATH.write_text(json.dumps(rec, indent=2) + "\n")
        print(f"floor recorded: {rec} -> {FLOOR_PATH}", file=sys.stderr)
        return 0
    if not FLOOR_PATH.exists():
        print(
            f"no recorded floor at {FLOOR_PATH}; run --record-floor",
            file=sys.stderr,
        )
        return 0  # missing floor is not a failure — first run records
    floor = json.loads(FLOOR_PATH.read_text())
    mismatched = {
        k: (floor.get(k), v)
        for k, v in config.items()
        if floor.get(k) != v
    }
    if mismatched:
        print(
            "floor check skipped: recorded floor does not match this "
            f"configuration ({mismatched}); re-record with --record-floor",
            file=sys.stderr,
        )
        return 0
    factor = float(os.environ.get("SWARM_FLOOR_FACTOR", "2.0"))
    limit = floor["walk_rows_per_sec"] / factor
    if rate < limit:
        print(
            f"WALK FLOOR REGRESSION: {rate:.0f} rows/s < recorded floor "
            f"{floor['walk_rows_per_sec']:.0f} / {factor}",
            file=sys.stderr,
        )
        return 1
    print(
        f"walk floor ok: {rate:.0f} rows/s >= "
        f"{floor['walk_rows_per_sec']:.0f} / {factor}",
        file=sys.stderr,
    )
    return 0


def main():
    argv = sys.argv[1:]
    if "--check-floor" in argv or "--record-floor" in argv:
        if (
            "--check-floor" in argv
            and os.environ.get("SWARM_FLOOR_SKIP") == "1"
        ):
            print("walk floor check skipped (SWARM_FLOOR_SKIP=1)",
                  file=sys.stderr)
            return 0
        return run_floor(argv)

    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=3072)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--cprofile", action="store_true")
    ap.add_argument("--ab", action="store_true",
                    help="paired serial-vs-batched walk A/B "
                         "(bench.bench_walk_ab on this corpus)")
    ap.add_argument("--corpus", default=DEFAULT_CORPUS)
    args = ap.parse_args(argv)
    if args.corpus == DEFAULT_CORPUS and not os.path.isdir(args.corpus):
        args.corpus = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tests", "data", "templates",
        )

    import numpy as np

    from bench import bench_walk_ab, realistic_rows
    from swarm_tpu.fingerprints import load_corpus
    from swarm_tpu.ops.engine import MatchEngine

    t0 = time.time()
    templates, errors = load_corpus(args.corpus)
    print(f"corpus: {len(templates)} templates ({time.time()-t0:.1f}s)")

    if args.ab:
        res = bench_walk_ab(templates, n_rows=min(args.rows, 512))
        print(json.dumps(res, indent=2))
        return 0 if res["identical"] else 1

    eng = MatchEngine(
        templates, mesh=None, batch_rows=args.rows,
        max_body=4096, max_header=1024,
    )

    rng = np.random.default_rng(4242)
    batches = []
    for i in range(args.iters + 1):
        rows = realistic_rows(args.rows, seed=1000 + i)
        for r in rows:
            salt = bytes(rng.integers(97, 123, size=48, dtype=np.uint8))
            r.body = b"<!-- %s -->" % salt + r.body
        batches.append(rows)

    t0 = time.time()
    eng.match_packed(batches[0])
    print(f"compile+first batch: {time.time()-t0:.1f}s")
    eng.clear_content_memos()
    eng.match_packed(batches[0])  # warm

    s = eng.stats
    h0, u0, e0, i0, f0 = (
        s.host_confirm_seconds, s.unc_seconds, s.ext_seconds,
        s.insert_seconds, s.fixup_seconds,
    )
    prof = None
    if args.cprofile:
        import cProfile

        prof = cProfile.Profile()
        prof.enable()
    n = args.iters * args.rows
    best = None
    rounds = int(os.environ.get("ROUNDS", "5"))
    for _ in range(rounds):
        # fresh content every round: the memos must keep missing
        eng.clear_content_memos()
        h0, u0, e0, i0, f0 = (
            s.host_confirm_seconds, s.unc_seconds, s.ext_seconds,
            s.insert_seconds, s.fixup_seconds,
        )
        t0 = time.perf_counter()
        for b in batches[1:]:
            eng.match_packed(b)
        wall = time.perf_counter() - t0
        walk = s.host_confirm_seconds - h0
        cur = (walk, wall, s.unc_seconds - u0, s.ext_seconds - e0,
               s.insert_seconds - i0, s.fixup_seconds - f0)
        print(f"  round: walk {walk*1e3:.1f} ms ({n/walk:.0f} rows/s)")
        if best is None or cur[0] < best[0]:
            best = cur
    if prof is not None:
        prof.disable()
    walk, wall, unc, ext, ins, fix = best
    print(f"rows: {n}  wall {wall:.3f}s  BEST walk {walk*1e3:.1f} ms "
          f"({n/walk:.0f} rows/s)")
    print(f"  unc    {unc*1e3:8.1f} ms "
          f"(precompute {s.walk_precompute_seconds*1e3:.1f} ms, "
          f"{s.walk_batched_pairs} batched pairs — cumulative)")
    print(f"  ext    {ext*1e3:8.1f} ms "
          f"(enum {s.ext_enum_seconds*1e3:.1f} resolve "
          f"{s.ext_resolve_seconds*1e3:.1f} extract "
          f"{s.ext_extract_seconds*1e3:.1f} — cumulative)")
    print(f"  insert {ins*1e3:8.1f} ms")
    print(f"  fixup  {fix*1e3:8.1f} ms")
    if prof is not None:
        import pstats

        st = pstats.Stats(prof)
        st.sort_stats("cumulative").print_stats(35)
    return 0


if __name__ == "__main__":
    sys.exit(main())

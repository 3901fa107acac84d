"""Attribute fresh-walk extraction time per extractor pattern.

Wraps MatchEngine._accel_extract_regex + cpu_ref.extract_one with
timers, runs bench-shaped fresh batches, prints per-pattern totals.
"""

import os
import sys
import time
from collections import defaultdict

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("ROWS", "1024"))
ITERS = int(os.environ.get("ITERS", "4"))


def main():
    import numpy as np

    from bench import realistic_rows
    from swarm_tpu.fingerprints import load_corpus
    from swarm_tpu.ops import cpu_ref
    from swarm_tpu.ops.engine import MatchEngine

    templates, _ = load_corpus("/root/reference/worker/artifacts/templates")
    eng = MatchEngine(templates, mesh=None, batch_rows=ROWS,
                      max_body=4096, max_header=1024)

    acc = defaultdict(lambda: [0, 0.0])  # key -> [calls, seconds]

    orig_accel = MatchEngine._accel_extract_regex

    def timed_accel(ex, part):
        t0 = time.perf_counter()
        out = orig_accel(ex, part)
        acc[("rx", tuple(ex.regex)[:1])][0] += 1
        acc[("rx", tuple(ex.regex)[:1])][1] += time.perf_counter() - t0
        return out

    MatchEngine._accel_extract_regex = staticmethod(timed_accel)

    orig_eo = cpu_ref.extract_one

    def timed_eo(ex, row):
        t0 = time.perf_counter()
        out = orig_eo(ex, row)
        key = ("eo-" + ex.type, tuple(getattr(ex, "regex", ()) or ())[:1])
        acc[key][0] += 1
        acc[key][1] += time.perf_counter() - t0
        return out

    cpu_ref.extract_one = timed_eo

    rng = np.random.default_rng(4242)
    batches = []
    for i in range(ITERS + 1):
        rows = realistic_rows(ROWS, seed=1000 + i)
        for r in rows:
            salt = bytes(rng.integers(97, 123, size=48, dtype=np.uint8))
            r.body = b"<!-- %s -->" % salt + r.body
        batches.append(rows)

    eng.match_packed(batches[0])
    eng.clear_content_memos()
    eng.match_packed(batches[0])
    acc.clear()
    s = eng.stats
    h0, e0, u0 = s.host_confirm_seconds, s.ext_seconds, s.unc_seconds
    for b in batches[1:]:
        eng.match_packed(b)
    walk = s.host_confirm_seconds - h0
    print(f"walk {walk*1e3:.1f} ms  ext {(s.ext_seconds-e0)*1e3:.1f} "
          f"unc {(s.unc_seconds-u0)*1e3:.1f}  ({ITERS*ROWS/walk:.0f} rows/s)")
    total = sum(v[1] for v in acc.values())
    print(f"attributed extractor time: {total*1e3:.1f} ms")
    for k, (n, t) in sorted(acc.items(), key=lambda kv: -kv[1][1])[:20]:
        print(f"  {t*1e3:8.2f} ms  {n:6d}x  {k[0]:10s} {str(k[1])[:90]}")


if __name__ == "__main__":
    main()

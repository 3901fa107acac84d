#!/usr/bin/env python3
"""What the program's own spans cover of a traced run, and what a span costs.

    python3 benchmark/span_cover.py --workload census-fresh --seed 7 --seconds 51
    python3 benchmark/span_cover.py --cost

The first form makes one ``--trace 1`` run of the cell through
``run.py`` and prints its result line, then a last line
``{"span_cover": ...}``: ``lib/spans.py`` ``cover`` of the spans the
cell's driver lists (``SPANS``) over the same window, that is the
device's idle ms per call, the share of it inside the union of those
spans, the spans opened per call, and per span name its ms, the idle ms
it covers and its count. It is not a
benchmark metric: it says how much of the idle time the metrics that
read the spans can see.

The second prints ``{"span_cost": ...}``: µs per ``tracing.span()``
entered and left on this host, with operator tracing off and no
profiler session, while a session records, and with operator tracing on
and an attempt context bound.
"""

from __future__ import annotations

import io
import json
import shutil
import sys
import time
from contextlib import redirect_stdout
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from lib import spans  # noqa: E402
from lib import spec as speclib  # noqa: E402


def traced(argv, require_tpu: bool = True, root: Path = run.ROOT) -> tuple:
    """One ``--trace 1`` run of ``run.run_cell``: (exit code, its stdout,
    the cover of its window or None). The window's ``Run`` is the one
    the harness hands its metric readers."""
    seen: list = []
    reader = speclib.reader

    def keeping(root_, metric):
        read = reader(root_, metric)

        def read_and_keep(r):
            seen[:] = [r]
            return read(r)

        return read_and_keep

    speclib.reader = keeping
    out = io.StringIO()
    try:
        with redirect_stdout(out):
            rc = run.run_cell(list(argv) + ["--trace", "1"],
                              require_tpu=require_tpu, root=root)
    finally:
        speclib.reader = reader
    return rc, out.getvalue(), spans.cover(seen[0], seen[0].program_spans) if seen else None


def cost(n: int = 20_000) -> dict:
    """µs per span in the three states (see the module's docstring)."""
    sys.path.insert(0, str(run.ROOT))
    import jax

    import swarm_tpu.ops.match  # noqa: F401  installs the profiler hook
    from swarm_tpu.telemetry import tracing

    def per_span(bind: bool) -> float:
        total = 0.0
        for k in range(0, n, 1000):
            # a fresh context per 1,000 spans: a context holds at most
            # TraceContext.MAX_SPANS, and past that drops instead of adding
            ctx = tracing.TraceContext("span-cost") if bind else None
            with tracing.activate(ctx):
                t0 = time.perf_counter()
                for _ in range(min(1000, n - k)):
                    with tracing.span("classifier.classify"):
                        pass
                total += time.perf_counter() - t0
        return total / n * 1e6

    tracing.set_enabled(False)
    try:
        off = per_span(False)
        where = HERE / ".cache" / "span_cost"
        shutil.rmtree(where, ignore_errors=True)
        with jax.profiler.trace(str(where)):
            session = per_span(False)
        shutil.rmtree(where, ignore_errors=True)
        tracing.set_enabled(True)
        operator_on = per_span(True)
    finally:
        tracing.set_enabled(None)
    return {"device": jax.devices()[0].device_kind, "spans": n,
            "off_no_session_us": off, "session_us": session,
            "operator_on_us": operator_on}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv == ["--cost"]:
        print(json.dumps({"span_cost": cost()}), flush=True)
        return 0
    rc, out, cov = traced(argv)
    sys.stdout.write(out)
    print(json.dumps({"span_cover": cov}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

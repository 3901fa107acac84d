#!/usr/bin/env python3
"""One run of one cell: ``--workload --seed --seconds --trace``.

The cell's configuration names a driver, ``benchmark/drivers/<driver>.py``,
which knows the deployment; this file is the same for every deployment.
Set-up builds the deployment (the lowered-DB cache and JAX's compile
cache sit in fixed directories inside the checkout), warms the cell's
shapes on warm-up calls of its own traffic, and generates the window's
calls ahead. The window then runs ``--seconds`` of back-to-back calls of
the timed path, one caller, closed loop. Afterwards, outside set-up and
the window, the driver's plain reference is built and compared with
what the window answered: a sample of rows drawn from the seed, or,
where the reference answers whole calls, every row of calls drawn from
the seed. The last stdout line is the result.

A driver module has a tuple and two functions. ``SPANS`` names the spans
the deployment's program opens (``breakdown`` names the device's idle
gaps from them, ``span_cover.py`` covers them; empty where it opens
none). ``build(root, config, mix, seed)`` builds the program as the
system does and returns the deployment:

- ``program``: the object whose call the window times (``--control`` and
  the tests' ``plant`` act on it);
- ``stats()``: the deployment's counters, a dict of numbers that only
  grow. ``device_compiles`` is required: warm-up runs until a call
  compiles nothing, and a compile in the window is reported. Where
  ``device_faults`` or ``degraded_batches`` move in a call, its rows
  count as failed. Metric readers get the whole dict before and after
  the window (``Run.stats0``, ``Run.stats1``);
- ``notes()`` (optional): lines printed to stderr after the window;
- ``call(stream, k)``: call ``k`` of the ``"warmup"`` or ``"window"``
  stream, a pure function of the seed, as ``(inputs, rows, sizes)``:
  what the timed call takes, each row as the reference reads it, and
  each row's unpadded input bytes;
- ``run(program, inputs)``: one call of the timed path, one output per
  row it answered; the outputs of the calls compared whole are read
  after the window, so later calls must leave them as they were;
- ``answer(output)``: what of an output has to equal the reference;
- ``control()``: the control, put in the program's place.

``reference(root, config)`` builds the plain reference, which imports
nothing of the program. It has ``expected(row)``, a row's answer, where
a row's answer depends on the row alone, or ``expected_call(rows)``, the
answers to a whole call, where it depends on the call (a cluster label
does). It may have ``verdict_width``, the verdicts per row that the
matcher's bytes floor counts.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
CACHE = HERE / ".cache"
sys.path.insert(0, str(HERE))

from lib import spec as speclib  # noqa: E402

#: rows kept per call for the comparison, and the most compared in a run
SAMPLE_PER_CALL = 128
SAMPLE_MAX = 768
#: calls kept whole for a reference that answers whole calls
WHOLE_CALLS = 2
#: the longest window a --trace 1 run traces: the profiler's export of the
#: matcher's op events, and reading it back, have to end within a run's limit
TRACE_SECONDS = 8.0


class RunError(Exception):
    pass


@dataclass
class Run:
    """What a metric reader reads (benchmark/metrics/<name>.py)."""

    workload: str
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    rows: int = 0
    calls: int = 0
    stats0: dict = field(default_factory=dict)
    stats1: dict = field(default_factory=dict)
    trace: list | None = None
    window_ns: tuple = (0, 0)
    devices: list = field(default_factory=list)
    peaks: dict = field(default_factory=dict)
    call_bytes: list = field(default_factory=list)
    verdict_width: int | None = None  # the reference's, where it has one
    program_spans: tuple = ()  # the driver's SPANS


def _bad(stats: dict) -> int:
    """The deployment's fault counters, where it keeps them."""
    return stats.get("device_faults", 0) + stats.get("degraded_batches", 0)


def _prepare(dep, seed: int, i: int, np):
    """Window call ``i``: its inputs and rows, the rows kept for the
    comparison (a seeded sample and the longest), their sizes and the
    call's input bytes."""
    inputs, rows, sizes = dep.call("window", i)
    pick = np.random.default_rng([seed, 7, i]).choice(
        len(rows), size=min(SAMPLE_PER_CALL, len(rows)), replace=False)
    keep = sorted(set(pick.tolist()) | {int(np.argmax(sizes))})
    return inputs, rows, sizes, keep, sum(sizes)


def _sampled(ref, kept: list, seed: int, np) -> list:
    """(call, row, size, program's answer, reference's) of up to
    ``SAMPLE_MAX`` kept rows drawn from the seed, with the longest."""
    rng = np.random.default_rng([seed, 8])
    longest = max(range(len(kept)), key=lambda j: kept[j][3])
    chosen = set(rng.choice(len(kept), size=min(SAMPLE_MAX, len(kept)), replace=False).tolist())
    chosen.add(longest)
    return [(call, r, size, got, ref.expected(row))
            for call, r, row, size, got in (kept[j] for j in sorted(chosen))]


def _whole(ref, kept_whole: dict) -> list:
    """The same for every row of the calls kept whole, each call's
    answers from ``ref.expected_call``."""
    out = []
    for call, (rows, sizes, got) in sorted(kept_whole.items()):
        want = ref.expected_call(rows)
        if len(want) != len(rows):
            raise RunError(f"the reference answered {len(want)} of call {call}'s {len(rows)} rows")
        out += [(call, r, sizes[r], got[r] if r < len(got) else None, want[r])
                for r in range(len(rows))]
    return out


def run_cell(argv=None, require_tpu: bool = True, plant=None, root: Path = ROOT) -> int:
    """One run; returns the exit code. ``plant(program)`` may break the
    timed path after set-up, ``require_tpu=False`` lets a run use the CPU
    and ``root`` names another tree's BENCHMARK.json and data: the tests'
    hooks."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="the driver's control in the program's place "
                         "(not a benchmark run)")
    args = ap.parse_args(argv)
    try:
        result = _run(args, root, require_tpu, plant)
    except (RunError, speclib.SpecError) as e:
        print(f"run failed: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


def _run(args, root: Path, require_tpu: bool, plant) -> dict:
    if not (ROOT / "swarm_tpu").is_dir():
        raise RunError(f"no swarm_tpu package in {ROOT}: nothing to measure")
    cell = speclib.load(root, args.workload)
    cfg, mix = cell.config, cell.mix
    chips = int(cell.workload["chips"])
    # fixed cache directories inside the checkout: a warm run loads the
    # lowered DB and every executable instead of building them
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "xla")
    os.environ["SWARM_DB_CACHE_DIR"] = str(CACHE / "db")
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))  # not /tmp
    sys.path.insert(0, str(ROOT))

    import jax
    import numpy as np

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise RunError(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    used = devs[:chips]
    peaks_all = json.loads((HERE / "lib" / "peaks.json").read_text())
    kind = used[0].device_kind
    if require_tpu and kind not in peaks_all["devices"]:
        raise RunError(f"device kind {kind!r} is not in lib/peaks.json")

    from swarm_tpu.utils.xlacache import enable_compilation_cache

    enable_compilation_cache()  # as the worker does at start-up
    dep = cell.driver.build(root, cfg, mix, args.seed)
    if "device_compiles" not in dep.stats():
        raise RunError("the driver's stats() counts no device_compiles")

    # warm the cell's own shapes on warm-up calls of its own traffic,
    # until no call has compiled for ``warmup_quiet_calls`` calls
    quiet, k, warm_t = 0, 0, []
    while not args.control and (k < mix["warmup_calls"] or quiet < mix["warmup_quiet_calls"]):
        before = dep.stats()["device_compiles"]
        t0 = time.perf_counter()
        dep.run(dep.program, dep.call("warmup", k)[0])
        warm_t.append(time.perf_counter() - t0)
        quiet = quiet + 1 if dep.stats()["device_compiles"] == before else 0
        k += 1
        if k >= mix["warmup_max_calls"]:
            break
    warm_calls, warm_compiles = k, dep.stats()["device_compiles"]
    # the window's calls, generated ahead from the steady warm-up pace
    pace = sorted(warm_t[-max(1, mix["warmup_quiet_calls"]):]) or [1.0]
    per_call = pace[len(pace) // 2]
    n_ahead = math.ceil(args.seconds / per_call * 1.3) + 2
    ahead = [_prepare(dep, args.seed, i, np) for i in range(n_ahead)]

    program = dep.control() if args.control else dep.program
    if plant is not None:
        plant(program)
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    # the calls kept whole for a reference that answers whole calls: call
    # 0, and others drawn from the seed among the first half of the calls
    # that the warm-up's pace says the window completes, so that a window
    # up to twice as slow still completes them
    n_pace = max(1, int(seconds / per_call / 2))
    whole = {0} | set(np.random.default_rng([args.seed, 9]).choice(
        np.arange(1, n_pace), size=min(WHOLE_CALLS, n_pace) - 1, replace=False).tolist())
    run = Run(workload=args.workload, seconds=seconds,
              peaks=peaks_all["devices"].get(kind, {}),
              devices=[d.id for d in used], program_spans=tuple(cell.driver.SPANS))
    trace_dir = CACHE / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    # ---------------------------------------------------------------- window
    kept: list = []  # (call, row index, row, size, the program's answer)
    kept_whole: dict = {}  # call -> (its rows, their sizes, the program's outputs)
    missing = failed = generated_late = 0
    run.stats0 = dep.stats()
    win0 = time.perf_counter()
    run.setup_s = time.monotonic() - T_START
    with jax.profiler.TraceAnnotation("bench.window"):
        i = 0
        while True:
            if i < len(ahead):
                inputs, rows, sizes, keep, nbytes = ahead[i]
            else:
                inputs, rows, sizes, keep, nbytes = _prepare(dep, args.seed, i, np)
                generated_late += 1
            bad0 = _bad(dep.stats())
            out = dep.run(program, inputs)
            t1 = time.perf_counter()
            if _bad(dep.stats()) != bad0:
                failed += len(rows)
            missing += max(0, len(rows) - len(out))
            for j in keep:
                got = dep.answer(out[j]) if j < len(out) else None
                kept.append((i, j, rows[j], sizes[j], got))
            if i in whole:
                kept_whole[i] = (rows, sizes, out)
            run.rows += len(rows)
            run.call_bytes.append(nbytes)
            i += 1
            if t1 - win0 >= run.seconds:
                break
    run.window_s = time.perf_counter() - win0
    run.calls = i
    run.stats1 = dep.stats()
    del ahead

    if args.trace:
        jax.profiler.stop_trace()
        from lib import trace as tr

        files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise RunError("the profiler wrote no trace")
        run.trace = tr.from_profile_data(jax.profiler.ProfileData.from_file(str(files[-1])))
        shutil.rmtree(trace_dir, ignore_errors=True)
        win = tr.host_spans(run.trace, ["bench.window"])
        if not win:
            raise RunError("the trace holds no bench.window span")
        run.window_ns = (win[0][1], win[0][2])
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    notes = dep.notes() if hasattr(dep, "notes") else []
    kept_whole = {i: (rows, sizes, [dep.answer(o) for o in out])
                  for i, (rows, sizes, out) in kept_whole.items()}
    del dep, program

    # ------------------------------------------------------------- checks
    print(f"window: {run.calls} calls, {run.rows} rows, {run.window_s} s; "
          f"set-up {run.setup_s} s; warm-up {warm_calls} calls, "
          f"{warm_compiles} device compiles; {n_ahead} calls generated ahead, "
          f"{generated_late} in the window", file=sys.stderr)
    print(f"device_compiles_in_window: "
          f"{run.stats1['device_compiles'] - run.stats0['device_compiles']}", file=sys.stderr)
    print(f"peak_bytes_in_use: {peak}", file=sys.stderr)
    for line in notes:
        print(line, file=sys.stderr)
    t_ref = time.perf_counter()
    ref = cell.driver.reference(root, cfg)
    run.verdict_width = getattr(ref, "verdict_width", None)
    if hasattr(ref, "expected_call"):
        compared = _whole(ref, kept_whole)
    else:
        compared = _sampled(ref, kept, args.seed, np)
    mismatched = 0
    for call, r, size, got, want in compared:
        if got != want:
            mismatched += 1
            if mismatched <= 5:
                print(f"mismatch call {call} row {r} ({size} B): "
                      f"program {got} reference {want}", file=sys.stderr)
    print(f"reference: built and {len(compared)} rows compared in "
          f"{time.perf_counter() - t_ref} s", file=sys.stderr)
    checks = {
        "mismatched_rows": {"value": mismatched, "limit": 0},
        "missing_rows": {"value": missing, "limit": 0},
    }
    correct = mismatched == 0 and missing == 0 and len(compared) > 0
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"check compared_rows: {len(compared)} (limit > 0)", file=sys.stderr, flush=True)
    checks["compared_rows"] = {"value": len(compared), "limit": "> 0"}

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = speclib.reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": run.rows, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        from lib import summary

        busy_s, window_s, breakdown = summary.device_summary(run)
        device["busy_s"], device["window_s"] = busy_s, window_s
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


if __name__ == "__main__":
    sys.exit(run_cell())

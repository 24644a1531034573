"""One run of one cell: set-up, the measured window, the check.

``run_cell`` does everything but look for the chip (``run.py`` does that
first), so that tests can drive a whole run on the CPU at a small size.

* Set-up: the inputs on the device from the seed, the strategy built
  once, and one set-up trial of ``check_rounds`` rounds through the same
  object and call as the window. It compiles init, the round, evaluation
  and the finite check, and its outputs are what the check compares.
  The peak memory after it is the memory one whole trial needs.
* Window: back-to-back trials of ``rounds`` rounds, each with its own key
  and participation seed from the seed and the trial's index. Trials
  start until ``seconds`` have passed; the window ends when the last one
  finishes.
* Traced run: after the window, one more trial under the profiler.
* Check: once the window has closed, the peak memory has been read and
  the program's state is freed, the cell's check module
  (``bench/checks/``) runs the reference over the set-up trial's rounds
  from the same inputs and seeds.

The ``run`` dict the metric readers get holds ``trials`` (per trial:
``wall_s``, ``eval_s``, ``host_s``, ``rounds``, ``evals``), ``window_s``,
``setup_s``, ``setup_peak_bytes`` (peak after the set-up trial),
``peak_bytes`` (peak after the window), ``config``, ``traffic``, ``peak``
(the chip's row of peaks.json), and ``trace`` (see ``trace.reduce``),
None without ``--trace 1``.
"""
from __future__ import annotations

import contextlib
import sys
import time

import jax

from bench import check, datagen, program, spec
from bench import trace as tracing


@contextlib.contextmanager
def compile_counter():
    """Counts XLA programs compiled or loaded from the persistent cache
    (``programs``), cache misses (``misses``) and their seconds."""
    box = {"programs": 0, "misses": 0, "seconds": 0.0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["programs"] += 1
            box["seconds"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            box["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def memory(devices) -> tuple:
    """(bytes in use, peak bytes in use) of the fullest device; 0 where
    the backend does not report them."""
    stats = [d.memory_stats() or {} for d in devices]
    return (max(s.get("bytes_in_use", 0) for s in stats),
            max(s.get("peak_bytes_in_use", 0) for s in stats))


def memory_line(devices) -> str:
    in_use, peak = memory(devices)
    return f"{in_use} bytes in use, peak {peak}"


def compare(cell, prog, arrays, params0, key, pseed, **ref_kw):
    """(correct, checks, numbers) of program outputs against the float32
    reference (or of the reference with ``ref_kw`` in the program's place),
    by the cell's check module."""
    values = spec.checker(cell).numbers(cell, prog, arrays, params0, key,
                                        pseed, **ref_kw)
    correct, checks = check.judge(values, cell.limits)
    return correct, checks, values


def run_cell(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
             peak: dict, trace_dir, log=None, strategy_hook=None) -> dict:
    """One run; returns the result line's fields (see the module doc).

    ``strategy_hook``, for tests, replaces the built strategy with what it
    returns, to break the timed path underneath."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    traf = cell.traffic
    devices = jax.devices()[:cell.chips]

    arrays, params0 = datagen.generate(cell.config, seed)
    jax.block_until_ready((arrays, params0))
    log(f"bench: inputs made, {memory_line(devices)}")
    data = program.federated_data(arrays)
    strategy = program.build(cell, params0)
    if strategy_hook is not None:
        strategy = strategy_hook(strategy)

    key0, pseed0 = datagen.trial_seeds(seed, 0)
    with compile_counter() as setup_compiles:
        hist, _ = program.run_trial(strategy, data, key0, pseed0, traf,
                                    traf["check_rounds"],
                                    traf["check_rounds"])
        prog = spec.checker(cell).outputs(strategy, hist)
        del hist
    setup_s = time.perf_counter() - t_start
    setup_peak = memory(devices)[1]
    log(f"bench: set-up {setup_s:.3f} s, {setup_compiles['programs']} "
        f"programs compiled or loaded, {setup_compiles['misses']} cache "
        f"misses, {memory_line(devices)}")

    trials = []
    with compile_counter() as window_compiles:
        t0 = time.perf_counter()
        while not trials or time.perf_counter() - t0 < seconds:
            key, pseed = datagen.trial_seeds(seed, len(trials) + 1)
            hist, host_s = program.run_trial(strategy, data, key, pseed, traf,
                                             traf["rounds"],
                                             traf["eval_every"])
            trials.append(program.timings(hist, host_s, traf["rounds"]))
            del hist
        window_s = time.perf_counter() - t0
    log(f"bench: window {window_s:.3f} s, {len(trials)} trials, "
        f"{window_compiles['programs']} programs compiled or loaded in it, "
        f"{window_compiles['misses']} cache misses, {memory_line(devices)}")
    peak_in_use = memory(devices)[1]

    reduction = None
    if trace:
        key, pseed = datagen.trial_seeds(seed, len(trials) + 1)
        _, events = tracing.capture(
            lambda: program.run_trial(strategy, data, key, pseed, traf,
                                      traf["rounds"], traf["eval_every"]),
            trace_dir)
        tracing.save(events, trace_dir / "events.json.gz")
        reduction = tracing.reduce(events)
    del strategy

    correct, checks, values = compare(cell, prog, arrays, params0, key0,
                                      pseed0)
    run = {"trials": trials, "window_s": window_s, "setup_s": setup_s,
           "setup_peak_bytes": setup_peak, "peak_bytes": peak_in_use,
           "config": cell.config, "traffic": traf,
           "peak": peak, "trace": reduction}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_in_use}
    result = {"correct": bool(correct), "attempted": len(trials),
              "failed": 0, "metrics": metrics, "device": device,
              "compiles": {"setup_programs": setup_compiles["programs"],
                           "setup_cache_misses": setup_compiles["misses"],
                           "window_programs": window_compiles["programs"]}}
    if reduction is not None:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = tracing.breakdown(reduction)
    for name, v in values.items():
        limit = checks[name]["limit"] if name in checks else "not compared"
        log(f"check {name} = {v!r} (limit {limit})")
    result["checks"] = checks
    return result

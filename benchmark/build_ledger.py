"""The program's build ledger (``horovod_tpu.trace.build_ledger()``,
docs/timeline.md "The build ledger") as two benchmark readers see it: which
compile-or-load ended inside the measured window, and where set-up went before
it. Pure functions over the ledger's plain data, so a hand-made ledger tests
them; :func:`read` returns ``None`` where the program keeps no ledger (a
parent commit from before it), and the readers then report nothing."""

from __future__ import annotations

import sys

TOP_FUNCTIONS = 8
PHASES = ("trace", "lower", "compile")


def read():
    try:
        from horovod_tpu import trace
    except ImportError:
        return None
    ledger = getattr(trace, "build_ledger", None)
    return ledger() if ledger is not None else None


def compiles_in(ledger, start_s, end_s):
    """Compile-or-load records that ended in ``[start_s, end_s]`` on
    ``time.perf_counter()``, the window's own clock."""
    return [r for r in ledger["compiles"] if r["phase"] == "compile"
            and start_s <= r["end_perf_s"] <= end_s]


def _covered_s(records):
    """Seconds the records cover, each instant once: an operation run at
    trace time is traced, lowered and compiled inside its caller's trace, so
    durations summed would count those seconds twice."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted((r["end_perf_s"] - r["dur_s"], r["end_perf_s"])
                             for r in records):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def setup(ledger, window_start_s, process_start_s=None):
    """Set-up by phase, over the records that ended before the window.
    ``accounted_s`` is the import and every instant some phase covers;
    ``before_window_s`` runs from the ledger's first stamp to the window, so
    the difference is what the ledger cannot name (the device work of the
    check steps and the warm-up, weights made on the device, the feed).
    ``top`` rows are ``[fun, trace_s, lower_s, compile_s]``, durations summed
    (of traces the ledger keeps the outermost, which holds its callees')."""
    before = [r for r in ledger["compiles"]
              if r["end_perf_s"] <= window_start_s]
    of = lambda phase: [r for r in before if r["phase"] == phase]
    by_fun = {}
    for r in before:
        row = by_fun.setdefault(r["fun"], dict.fromkeys(PHASES, 0.0))
        row[r["phase"]] += r["dur_s"]
    top = sorted(by_fun.items(), key=lambda kv: -sum(kv[1].values()))
    import_s = ledger["import_s"] or 0.0
    first = ledger["first_perf_s"]
    programs = of("compile")
    missed = sorted((r for r in programs if r.get("cache") == "miss"),
                    key=lambda r: -r["dur_s"])
    line = {
        "import_s": import_s,
        "trace_s": _covered_s(of("trace")),
        "lower_s": _covered_s(of("lower")),
        "compile_or_load_s": _covered_s(programs),
        # of the programs before the window; the ledger's own counters run on
        # through the reference's programs
        "cache_hits": sum(r.get("cache") == "hit" for r in programs),
        "cache_misses": len(missed),
        "cache_retrieval_s": sum(r.get("cache_retrieval_s", 0.0)
                                 for r in programs),
        "missed": [r["fun"] for r in missed[:TOP_FUNCTIONS]],
        "programs": len(programs),
        "accounted_s": import_s + _covered_s(before),
        "before_window_s": (None if first is None
                            else window_start_s - first),
        "top": [[fun] + [row[p] for p in PHASES]
                for fun, row in top[:TOP_FUNCTIONS]],
    }
    if process_start_s is not None and first is not None:
        # process start to the ledger's first stamp: the interpreter, `import
        # jax`, the runtime's start, the batches; with before_window_s it is
        # the run's setup_s
        line["before_ledger_s"] = first - process_start_s
    return _rounded(line)


def _rounded(value):
    if isinstance(value, float):
        return round(value, 4)
    if isinstance(value, list):
        return [_rounded(v) for v in value]
    if isinstance(value, dict):
        return {k: _rounded(v) for k, v in value.items()}
    return value


def process_start():
    """``benchmark/run.py``'s own first stamp, where it is the process."""
    return getattr(sys.modules.get("__main__"), "_PROCESS_START", None)

"""Programs compiled (or loaded from the compile cache) inside the measured
window: the build ledger's ``compile`` records whose end lies between
``window_start`` and ``window_end`` on the host's ``perf_counter``. A sound
run reads 0: every shape is warmed up in set-up, and a compile in the window
stalls the launch queue. Source: the program's counter
(``horovod_tpu.trace.build_ledger()``); nothing where the program keeps none.

Also prints the line ``setup: {...}``: set-up before the window by phase
(import, tracing, lowering, compile-or-load, the cache's hits and misses and
the functions that missed) and the eight costliest functions
(``benchmark/build_ledger.py``)."""

import json

from benchmark import build_ledger


def compute(run):
    ledger = build_ledger.read()
    if ledger is None or run.window_start is None:
        return None
    print("setup: " + json.dumps(build_ledger.setup(
        ledger, run.window_start, build_ledger.process_start())), flush=True)
    inside = build_ledger.compiles_in(ledger, run.window_start,
                                      run.window_end)
    if inside:
        print("compiled_in_window: " + json.dumps(
            [[r["fun"], round(r["dur_s"], 4)] for r in inside]), flush=True)
    return len(inside)

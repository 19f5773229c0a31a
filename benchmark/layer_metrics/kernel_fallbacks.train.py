"""Kernel call sites of this process's programs that took the XLA form of a
Pallas kernel (``horovod_tpu.trace.note_fallback``: one record a traced call
site, with the kernel's name and why), counted after the window. 0 where
every kernel the cell should run runs; a by-name kernel reader that returns
nothing beside a count here has lost its kernel, beside a 0 its name. Source:
the program's counter (``horovod_tpu.trace.build_ledger()``); nothing where
the program keeps none.

Also prints ``plans: {...}`` (the plan notes: tiles, forms and grids the
kernels and the exchange chose at trace time) and ``fallbacks: [...]``."""

import json

from benchmark import build_ledger


def compute(run):
    ledger = build_ledger.read()
    if ledger is None:
        return None
    print("plans: " + json.dumps(ledger["plans"], default=str), flush=True)
    print("fallbacks: " + json.dumps(ledger["fallbacks"], default=str),
          flush=True)
    return len(ledger["fallbacks"])

"""The part of the collectives' time per step during which no other operation
runs on that chip: what the exchange adds to the step. Median over the traced
steps, chip 0. Source: device trace."""

from benchmark import manifest


def compute(run):
    return manifest.load_reader("grad_collective_ms").compute(run,
                                                              exposed=True)

"""Share of the engine's decision time, in %, spent fitting GPHPs:
the sum of suggest.gphp_fit spans over the sum of suggest.decide spans."""

from bench.metrics._spans import by_name


def read(run):
    fit = sum(s["dur"] for s in by_name(run, "suggest.gphp_fit"))
    decide = sum(s["dur"] for s in by_name(run, "suggest.decide"))
    return 100.0 * fit / decide if fit > 0 and decide > 0 else None

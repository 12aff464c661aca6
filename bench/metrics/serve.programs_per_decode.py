"""Device programs a decode step runs: those whose run starts inside a
``serve.decode`` span, moved onto the device's clock (``bench.spans``),
over the number of those spans. Each is a dispatch of its own, with the
chip idle between."""
from bench import spans


def read(run):
    # a trace read without the program's spans holds none to read
    if run.trace is None or not getattr(run.trace, "program_spans", None):
        return None
    return spans.programs_per_decode(run.trace)

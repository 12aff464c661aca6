"""Steps the host held up: ``serve.prefill`` and ``serve.decode`` spans of
the window longer than twice the median of their kind (``bench.spans``)."""
from bench import spans


def read(run):
    # a trace read without the program's spans holds none to read
    if run.trace is None or not getattr(run.trace, "program_spans", None):
        return None
    return spans.stalls(run.trace)

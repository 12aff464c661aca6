"""Host time of a decode step that the chip waits through: the median,
over the window's decode steps, of the chip's idle ms inside the step's
``serve.decode`` span, moved onto the device's clock (``bench.spans``)."""
from bench import spans


def read(run):
    # a trace read without the program's spans holds none to read
    if run.trace is None or not getattr(run.trace, "program_spans", None):
        return None
    return spans.decode_host_ms(run.trace)

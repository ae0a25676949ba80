"""Host time (ms per checkpoint) the cohort serve loop spends taking the
checkpoint's payload to the host (``ckpt.payload``: the device carry and a
copy of the fleet's host plane), per ``serve.checkpoint`` in the traced
window."""
from program_spans import per_checkpoint_ms


def read(run):
    return per_checkpoint_ms(run.trace, run.window, "ckpt.payload")

"""Host time (ms per checkpoint) the cohort serve loop spends writing the
checkpoint (``ckpt.write``: the npz payload with its fsync and rename, then
the manifest), per ``serve.checkpoint`` in the traced window."""
from program_spans import per_checkpoint_ms


def read(run):
    return per_checkpoint_ms(run.trace, run.window, "ckpt.write")

"""Host time (ms per checkpoint) the cohort serve loop spends reading the
written payload back for its sha256 (``ckpt.digest``), per
``serve.checkpoint`` in the traced window."""
from program_spans import per_checkpoint_ms


def read(run):
    return per_checkpoint_ms(run.trace, run.window, "ckpt.digest")

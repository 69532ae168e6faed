"""Trainer: device milliseconds of the jitted GRPO train step in the trace
(XLA program ``jit_train_step``) per step run in the traced window."""


def read(w):
    if w.trace is None:
        return None
    sec, n = w.trace.time_of("train_step", modules=True)
    return 1e3 * sec / n if n else None

"""The whole model's share (%) of the chip's peak over the traced window:
the operations the algorithm needs for every prompt token prefilled and row
decoded (``bench/work/model_step.py``) over window x chips x peak."""


def read(run):
    return run.mfu()

"""The whole step's share of the chips' peak: model FLOPs of the tokens
the traced steps processed, over the sub-window x chips x peak."""


def read(run):
    return run.step_mfu()

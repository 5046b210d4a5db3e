"""What the trainer does between step calls, per step in ms: the window's
wall time per step minus the mean of ``Trainer.step_seconds`` over the
same steps (the step call, its device sync and the loss read)."""


def read(run):
    w = run.window
    if not w.get("steps"):
        return None
    return (w["seconds"] / w["steps"] - w["step_seconds_mean"]) * 1e3

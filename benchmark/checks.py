"""The comparison that decides ``correct`` for a training job."""

import math


def judge_training(losses, epochs, commits, expected_commits, rules):
    """(correct, failed epochs, reasons).  ``losses`` is the job's mean loss
    per epoch; ``rules`` is the configuration's ``"correct"`` group:

    * every epoch has a finite loss (a missing or non-finite one is a failed
      epoch);
    * the center counted ``expected_commits`` commits, no more and no fewer
      (a lost or double-counted commit fails);
    * ``last_over_first_at_most``: the last epoch's loss over the first's;
    * ``first_loss_near`` (optional): ``[value, tolerance]`` for the first
      epoch, an untrained model's loss.
    """
    reasons = []
    failed = sum(1 for k in range(epochs)
                 if k >= len(losses) or not math.isfinite(losses[k]))
    if failed or len(losses) != epochs:
        reasons.append(f"{failed} of {epochs} epochs without a finite loss "
                       f"(history has {len(losses)})")
    if commits != expected_commits:
        reasons.append(f"the center counted {commits} commits, the job makes "
                       f"{expected_commits}")
    if not failed and len(losses) >= 2:
        ratio = losses[-1] / losses[0]
        if not ratio <= rules["last_over_first_at_most"]:
            reasons.append(
                f"last epoch's loss is {ratio:.4f} of the first's, allowed "
                f"{rules['last_over_first_at_most']}")
        if "first_loss_near" in rules:
            value, tolerance = rules["first_loss_near"]
            if not abs(losses[0] - value) <= tolerance:
                reasons.append(f"first epoch's loss {losses[0]:.4f} is not "
                               f"within {tolerance} of {value}")
    elif not failed:
        reasons.append("fewer than two epochs: the loss cannot be seen to fall")
    return not reasons, failed, reasons

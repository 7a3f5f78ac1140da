"""Layer: ``amp``.  Steps of the measured window whose update the loss scaler
skipped: steps run minus the optimizer's own count.  An exact count, so it is
read off the chip too."""


def read(run):
    return float(run.skipped_steps)

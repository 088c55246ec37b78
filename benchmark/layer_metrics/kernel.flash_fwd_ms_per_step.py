"""Device time of the flash-attention FORWARD kernel (``attn.flash_fwd``) per
train step, first device.  Under remat the forward runs twice per layer, so
the calls per step read 2 x layers."""
from benchmark import spans


def read(run):
    return spans.kernel_ms_per_train_step(run, "attn.flash_fwd")

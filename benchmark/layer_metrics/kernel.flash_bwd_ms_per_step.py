"""Device time of the flash-attention BACKWARD kernels (``attn.flash_dq`` +
``attn.flash_dkv``) per train step, first device."""
from benchmark import spans


def read(run):
    return spans.kernel_ms_per_train_step(run, "attn.flash_dq",
                                          "attn.flash_dkv")

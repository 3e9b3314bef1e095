"""Operations the served (TT-compressed) model needs for the prompt and
output tokens the device processed in the traced window (counted by the
configuration's ops module, ``sequence_flops``: padding rows and unneeded
logits excluded), over the window's seconds times the chip's peak."""
import devtrace


def read(run):
    return devtrace.mfu(run)

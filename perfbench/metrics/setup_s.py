"""setup_s: seconds from the start of the process to the first measured
call: interpreter and JAX start-up, the field made from the seed, the
traffic's own set-up (the ROI store), and every program compiled or loaded
from the persistent cache by the warm-up."""


def read(ctx):
    return ctx.setup_s

"""Mean host time per batch of the consumer's finalize less its H2D copy:
the table lookup, the int32 map conversions and the fused finalize's
dispatch (the
``finalize`` span's duration minus the ``h2d_staging`` span inside it on
the same thread), over the finalizes that ended inside the window."""
from benchlib.stages import self_ms


def read(run):
    return self_ms(run, "finalize", "h2d_staging")

"""sliced_bits: |S|, the number of sliced indices in the cell's plan.

Every slice id carries |S| bits and the job runs 2**|S| slices, so one
bit fewer halves ``full_contraction_s``.  Read from the plan's sliced
wires (``ctx["problem"]["sliced"]``); the program keeps the same number
as the gauge ``engine.sliced_bits``."""


def read(ctx):
    return len(ctx["problem"]["sliced"])

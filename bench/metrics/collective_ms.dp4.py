"""Device time per step of the all-reduce operations on device 0 (the
compressed gradient-difference psum and the pmax of the scales), in ms.
The part of it during which no other operation ran on that device is in
the run's breakdown and on its standard error."""
import sys


def read(run):
    s = run.summary
    if s is None or run.chips < 2 or not s.allreduce_s:
        return None
    steps = len(run.work)
    print(f"collective_ms.dp4: all-reduce {s.allreduce_s:.6f} s, exposed "
          f"{s.allreduce_exposed_s:.6f} s over {steps} steps",
          file=sys.stderr)
    return 1e3 * s.allreduce_s / steps

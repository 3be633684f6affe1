"""Host seconds of the program's AxPlan build: the destination-major
companion layout with its weight copy, read back from the placed slabs
(`build.ax_plan` span) (moves `setup_s`)."""
from bench.lib.annotations import build_span_s


def read(r):
    return build_span_s(r, "build.ax_plan")

"""Host seconds of the program's `pack_slabs`: the source-major slabs from
the edge lists (`build.pack` span) (moves `setup_s`)."""
from bench.lib.annotations import build_span_s


def read(r):
    return build_span_s(r, "build.pack")

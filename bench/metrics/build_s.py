"""Host seconds of the program's instance build: `pack_slabs`, the row
normalisation, placement and the AxPlan build (moves `setup_s`)."""


def read(r):
    return r.get("build_s")

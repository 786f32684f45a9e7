"""Share of the square the self-join's dense masks covered: the sum over
the window's calls of rows x band columns (``stats["band_cells"]``) over
rows x n. None where the program reports no bands."""


def read(run):
    r = run.record
    cells, full = r.get("band_cells"), r.get("full_cells")
    return cells / full if cells and full else None

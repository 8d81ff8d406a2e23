"""Bytes a seven-point stencil call must move, from its shape alone.

Copied from the paper's Eq. 1 (``repro.core.metrics``): every input cell
but the 8 corners and the 12 edges is fetched once, and every interior cell
is written once.  Boundary output cells are left out, as the paper's
kernels never write them.  A kernel that reads its input more than once
still needs only these bytes, so re-reads show as a lower share of the
roofline, not as a larger count.
"""


def bytes_required(shape, itemsize: int) -> float:
    nz, ny, nx = shape
    cells = nz * ny * nx
    corners = 8
    edges = 4 * ((nx - 2) + (ny - 2) + (nz - 2))
    fetch = (cells - corners - edges) * itemsize
    write = (nz - 2) * (ny - 2) * (nx - 2) * itemsize
    return float(fetch + write)

# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def default_interpret() -> bool:
    """The one interpret-mode policy for every Pallas kernel in this package:
    compiled on gpu/tpu, interpret (pure-XLA emulation) on cpu and anything
    else without a kernel-capable accelerator."""
    import jax

    return jax.default_backend() not in ("gpu", "tpu")


#: widest lane extent of one elementwise block (a multiple of the 128 lanes)
LANE_BLOCK = 2048
#: row granularity of a block: the sublane tile of bf16 (and a multiple of
#: f32's 8), so a block never splits a packed tile
ROW_BLOCK = 16


def _tile_grid(block, *arrays):
    """The one tiling recipe of the elementwise *_raw wrappers (guided_update
    and its optimizer-fused family). Every array is viewed as 2-D `(R, C)`:
    leading dims merge into rows and the last dim stays the lane dim, so a
    leaf in its native TPU layout (tiled over its last two dims) is read in
    place rather than relaid out into a flat vector. A 1-D array is one row.

    A block is `(rows, cols)` of about `block` elements: `cols` the whole last
    dim when it fits `LANE_BLOCK`, else `LANE_BLOCK`; `rows` the rest of the
    budget in multiples of `ROW_BLOCK`, or every row when they fit. Edge
    blocks may overhang the array; Pallas masks them.

    Returns `(views, block_shape, grid)`; the caller reshapes each output
    back to the leaf's shape.
    """
    shape = arrays[0].shape
    C = shape[-1] if shape else 1
    R = arrays[0].size // max(C, 1)
    views = [a.reshape(R, C) for a in arrays]
    cols = C if C <= LANE_BLOCK else LANE_BLOCK
    rows = max(block // cols, 1)
    if rows >= R:
        rows = R
    else:
        rows = max(rows // ROW_BLOCK * ROW_BLOCK, ROW_BLOCK)
        rows = min(rows, R)
    grid = (-(-R // rows), -(-C // cols))
    return views, (rows, cols), grid

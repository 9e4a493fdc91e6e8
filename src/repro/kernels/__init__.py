# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def default_interpret() -> bool:
    """The one interpret-mode policy for every Pallas kernel in this package:
    compiled on gpu/tpu, interpret (pure-XLA emulation) on cpu and anything
    else without a kernel-capable accelerator."""
    import jax

    return jax.default_backend() not in ("gpu", "tpu")


#: lane width of the TPU's vector registers: a block that splits a last dim
#: does so in multiples of it
LANE = 128
#: sublanes of a vector register: XLA tiles a leaf's last two dims (8, 128)
#: where the second-minor dim is a multiple of 8, and otherwise in a layout of
#: its own (bf16[..., 2, 11008] is tiled (2, 128))
SUBLANES = 8
#: row granularity of a block: the sublane tile of bf16 (and a multiple of
#: f32's 8), so a block never splits a packed tile
ROW_BLOCK = 16
#: fast memory the double-buffered in/out blocks of one kernel may take.
#: Compiling for a v5e accepts every block of the family whose streams come
#: to 12 MiB and refuses every one at 16 MiB: the kernel body's f32
#: temporaries need room beside the streams.
VMEM_STREAM_BYTES = 12 << 20
#: the largest block (elements) compiled for a v5e (tests/test_tpu_compile.py)
MAX_BLOCK = 524288


def stream_block(dtypes) -> int:
    """The element budget of one block of an elementwise kernel whose streams
    (every array in and out, one entry each) have `dtypes`: the largest power
    of two whose double-buffered blocks fit `VMEM_STREAM_BYTES`, at most
    `MAX_BLOCK`. A grid step costs a fixed overhead beside its DMA, so the
    largest block that fits streams closest to the bandwidth floor. The
    power of two leaves the kernel body's temporaries their room: momentum's
    bf16 streams at 393,216 elements (12 MiB) need 17 MiB of a v5e's 16."""
    import numpy as np

    per_elem = 2 * sum(np.dtype(d).itemsize for d in dtypes)  # x2: Pallas double-buffers
    fit = min(MAX_BLOCK, VMEM_STREAM_BYTES // per_elem)
    return 1 << (fit.bit_length() - 1)


def _lane_cols(C: int, max_cols: int) -> int:
    """Block width for a last dim of `C` when at most `max_cols` fit: all of
    it, else the fewest equal parts in multiples of `LANE` (a part then
    divides `C`, so no narrow edge block); where `C` is no multiple of
    `LANE`, equal parts rounded up to it, the last one masked."""
    if C <= max_cols:
        return C
    max_cols = max(max_cols // LANE * LANE, LANE)
    n = -(-C // max_cols)
    if C % LANE == 0:
        units = C // LANE
        while units % n:
            n += 1
        return C // n
    part = -(-C // n)
    return -(-part // LANE) * LANE


def tiling(shape, block: int):
    """How the elementwise *_raw wrappers (guided_update and its
    optimizer-fused family) tile a leaf of `shape` into blocks of at most
    about `block` elements.

    The leaf is viewed 3-D as `(L, M, C)` in its own HBM layout, so the view
    is a bitcast and never a relayout: where its second-minor dim is a
    multiple of `SUBLANES` (or the leaf has at most 2 dims), every leading dim
    merges into `M` (`L = 1`); elsewhere (`[..., 2, 11008]`) the last two
    dims stay as they are and the leading ones merge into `L`.

    A block spans the whole last dim where `ROW_BLOCK` rows of it (or all `M`
    rows, if fewer) fit the budget, else equal parts of it (`_lane_cols`).
    Where all `M` rows fit, the block takes as many of them as fit whole and
    as many `L` slices of them as fit; else `ROW_BLOCK` multiples of rows of
    one slice. Edge blocks may overhang the array; Pallas masks them.

    Returns `(view, block_shape, grid)`, each a 3-tuple.
    """
    C = shape[-1] if shape else 1
    lead = 1
    for d in shape[:-1]:
        lead *= d
    if len(shape) >= 3 and shape[-2] % SUBLANES:
        M = shape[-2]
    else:
        M = lead
    L = lead // M
    cols = _lane_cols(C, max(block // min(M, ROW_BLOCK), 1))
    if M * cols <= block:
        bs = (min(block // (M * cols), L), M, cols)
    else:
        bs = (1, min(max(block // cols // ROW_BLOCK * ROW_BLOCK, ROW_BLOCK), M), cols)
    view = (L, M, C)
    return view, bs, tuple(-(-v // b) for v, b in zip(view, bs))

"""guided_update_roofline: the HBM bytes one optimizer step needs
(`counts.update_bytes`: what the algorithm reads and writes, not what the
kernel happens to read), shared over the chips, at the chip's HBM peak,
over the fused kernels' time per step; in percent. Memory bounds the
elementwise update, so the byte roofline is its roofline."""
import counts
from metrics.guided_update_ms import seconds_per_step


def read(run):
    s = seconds_per_step(run)
    if s is None:
        return None
    need = counts.update_bytes(run.cfg, run.traffic) / run.chips
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / s

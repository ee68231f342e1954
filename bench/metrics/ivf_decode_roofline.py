"""The least time the chip needs for the bytes (or operations) of every
``ivf_decode`` call in the traced stretch, over the kernel's device time
there, in percent. Each call's bytes follow from its step's measured
probe-union size U (``head_live``), the block size, the tail sample and
the lane count (``bench.roofline.ivf_decode_cost``)."""
from bench.roofline import ivf_decode_cost, roofline_share

# the Pallas kernel's custom call takes the name of the jitted function
# that launches it, core.decode.mimps_decode
KERNEL = r"^%mimps_decode[.\d]* \(custom-call\)$"


def read(run):
    r = run.reduction
    if r is None:
        return None
    secs, n = r.ops_matching(KERNEL)
    steps = [s for s in run.steps if s.traced]
    if not n or not steps or n != len(steps):
        return None
    s = run.serving
    flops = nbytes = 0.0
    for st in steps:
        f, b = ivf_decode_cost(q=s["n_slots"], head_blocks=st.head_live,
                               block_rows=s["block_rows"], tail_rows=s["l"],
                               d=run.cell.arch.d)
        flops += f
        nbytes += b
    share, _ = roofline_share(flops, nbytes, secs, run.peaks)
    return share

"""The ``embed_lookup`` kernel's share of its roofline, in percent: the
least time the chip needs for the bytes the gathers need, over the
kernel's device time in the traced window.  A request needs its rows read
and written once (keys x dim x 4 B each way) and its ids read (keys x
4 B); no operation counts, so HBM bandwidth bounds it.  The one-hot sweep
of the whole shard that the kernel makes is not counted: a row-gather
kernel reads against the same yardstick.  A gather path without that
kernel reads nothing."""

KERNEL = "embed_lookup"


def bytes_per_request(keys: int, dim: int) -> int:
    return keys * (2 * dim * 4 + 4)


def read(run):
    t = run.trace
    if t is None or not run.retired or t.op_device_s.get(KERNEL, 0.0) <= 0:
        return None
    need = bytes_per_request(run.cell.traffic["keys_per_request"], run.cell.config["dim"])
    least_s = run.retired * need / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t.op_device_s[KERNEL]

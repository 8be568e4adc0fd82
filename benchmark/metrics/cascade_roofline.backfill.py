"""The cascade's share of the bandwidth roofline, in %: the least bytes
it must move (``roofline.least_bytes``) over the chip's peak HBM
bandwidth, over the device time of the cascade program's operations in
the profiler trace of one whole job."""

from benchmark import roofline


def read(run):
    prof = run.get("profile")
    if not prof or prof["cascade_device_s"] <= 0:
        return None
    return roofline.roofline_pct(prof["least_bytes"], prof["cascade_device_s"],
                                 roofline.peaks(prof["device_kind"])
                                 ["hbm_bytes_per_s"])

"""Pallas forward kernel device time per real frame
(`lib/readers.fwd_kernel_ns_per_frame`); the cells that report
`frames_per_s`."""

from lib.readers import fwd_kernel_ns_per_frame as read  # noqa: F401

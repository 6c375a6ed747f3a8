"""Bytes restored over the mean time of the benchmark's span around
``xdfs_ckpt.restore`` (device placement waited for), in MB/s."""
from harness.readers import restore_mb_s as read  # noqa: F401

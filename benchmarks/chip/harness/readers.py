"""Reductions that several per-layer metrics share, each under its own name
split by the end-to-end metric its cells report: the ``idle_share.*`` and
the ``restore_mb_s.*`` metrics."""
import statistics


def idle_share(run):
    """Share of the traced window in which no op ran on the device, mean
    over the chips, in %."""
    s = run.summary
    if s is None or not s.n_devices or s.window_s <= 0:
        return None
    return 100 * (1 - s.busy_mean_s / s.window_s)


def restore_mb_s(run):
    """The bytes a driver restores (its ``restored_bytes`` fact) over the
    mean time of its span around ``xdfs_ckpt.restore`` (device placement
    waited for), in MB/s."""
    s = run.span_total("restore")
    if not s or "restored_bytes" not in run.facts:
        return None
    return run.facts["restored_bytes"] / statistics.mean(s) / 1e6

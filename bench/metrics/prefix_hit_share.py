"""Prefix cache: the share of prompt pages admitted in the window that the
cache served, from the engine's own counters (``cache_stats()``: pages hit
in any tier, and pages that had to prefill)."""

HITS = ("hits_device_pages", "hits_host_pages", "hits_disk_pages")


def read(run):
    a, b = run.cache_start, run.cache_end
    if not a or not b:
        return None
    hits = sum(b[k] - a[k] for k in HITS)
    total = hits + b["miss_pages"] - a["miss_pages"]
    return None if total <= 0 else 100.0 * hits / total

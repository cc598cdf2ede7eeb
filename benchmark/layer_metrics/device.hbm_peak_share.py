"""Layer device. Peak bytes in use over the bytes the chip offers, on the
fullest chip: /stats `device.memory` (JAX's memory_stats) at the window's
end. Left out where the backend reports none."""


def read(run):
    mem = [m for m in run["stats1"].get("device", {}).get("memory") or [] if m.get("bytes_limit")]
    if not mem:
        return None
    return 100.0 * max(m["peak_bytes_in_use"] / m["bytes_limit"] for m in mem)

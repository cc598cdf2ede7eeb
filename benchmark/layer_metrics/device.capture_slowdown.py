"""Layer device. How much the profiler's capture slows the host it times:
1 - (device steps a second inside the `capture` span) / (device steps a
second in the window before the capture started), from the `device` spans
by their start; /spans, host clock of the node. The part of the window
after the capture is left out: the profiler is then writing its trace
(`capture_close`, tens of seconds on the chip), which slows the host more
than the capture does. Near 0 the capture's idle share can be read as the
untraced one."""

import spans


def read(run):
    cap = next((s for s in run["spans"] if s.get("name") == "capture"), None)
    dev = spans.named(run, "device")
    if cap is None or not dev:
        return None
    c0, c1 = max(cap["t0"], run["wall0"]), min(cap["t1"], run["wall1"])
    before = sum(1 for s in dev if s["t0"] < c0)
    if c1 <= c0 or c0 <= run["wall0"] or not before:
        return None
    inside = sum(1 for s in dev if c0 <= s["t0"] <= c1)
    return 100.0 * (1.0 - (inside / (c1 - c0)) / (before / (c0 - run["wall0"])))

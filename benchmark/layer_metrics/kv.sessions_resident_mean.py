"""Layer kv_manager. Sessions holding KV on the device, mean of /stats
`executor` polled once a second inside the window: `lanes_busy` under
--batch-lanes, `sessions` under --mesh."""


def read(run):
    seen = [ex.get("lanes_busy", ex.get("sessions")) for t, ex in run["polls"]
            if run["w0"] <= t <= run["w1"]]
    seen = [float(x) for x in seen if x is not None]
    return sum(seen) / len(seen) if seen else None

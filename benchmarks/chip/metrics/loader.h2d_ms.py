"""Host-device transfer (`core/tiers.py:DeviceStoreTier._probe_rows`):
wall time of the loader's `h2d` spans in the window, per window batch,
in ms: the host's time in the three copies of the padded ids, staged rows
and reuse counts to the device, until they return."""


def read(rec):
    d = [s for name, s in rec.spans if name == "h2d"]
    return 1e3 * sum(d) / len(rec.steps) if d else None

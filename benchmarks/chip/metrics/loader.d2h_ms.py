"""Host-device transfer (`core/tiers.py:DeviceStoreTier._probe_rows`):
wall time of the loader's `d2h` spans in the window, per window batch,
in ms: copying the padded gathered rows and the hit mask back to the
host."""


def read(rec):
    d = [s for name, s in rec.spans if name == "d2h"]
    return 1e3 * sum(d) / len(rec.steps) if d else None

"""Device tier (`core/tiers.py:DeviceStoreTier._probe_rows`,
`core/device_store.py:device_gather`): wall time of the loader's
`device_wait` spans in the window, per window batch, in ms: dispatching
`device_gather` and waiting until its rows and hit mask are ready, which
includes device work queued before it (`push_window`, the rest of the
upload)."""


def read(rec):
    d = [s for name, s in rec.spans if name == "device_wait"]
    return 1e3 * sum(d) / len(rec.steps) if d else None

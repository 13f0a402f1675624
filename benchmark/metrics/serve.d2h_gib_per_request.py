"""Serving: GiB pulled from the device to the host per released
request -- the server registry's ``serve.d2h_bytes`` counter (padded
ring arrays of every rollback snapshot, interiors of every response)
over the requests it released (``serve.requests.ok`` + ``.anomaly``),
warm-up and window together.  ``None`` where the program keeps no such
counter."""


def read(run):
    srv = getattr(run.cell.kind, "srv", None)
    if srv is None:
        return None
    counters = srv.obs.snapshot().get("counters", {})
    released = (counters.get("serve.requests.ok", 0)
                + counters.get("serve.requests.anomaly", 0))
    if "serve.d2h_bytes" not in counters or not released:
        return None
    return counters["serve.d2h_bytes"] / 2 ** 30 / released

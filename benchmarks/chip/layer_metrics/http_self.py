"""HTTP front end (``serve/server.py``): the part of a request's client-side
time that lies outside its server-side ``request`` trace (parsing, the
handler thread, writing the response, the socket), mean per answered
request, in ms.  Matched by the ``X-Trace-Id`` the load generator sends."""
NAME = "http.self_ms"


def read(ctx: dict):
    by_id = {t["trace_id"]: t for t in ctx["traces"]}
    gaps = []
    for r in ctx["requests"]:
        trace = by_id.get(r["trace_id"])
        if not r["answered"] or trace is None:
            continue
        gaps.append((r["done"] - r["fired"]) - trace["duration_s"])
    return 1e3 * sum(gaps) / len(gaps) if gaps else None

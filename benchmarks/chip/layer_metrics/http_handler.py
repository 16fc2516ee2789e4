"""HTTP front end (``serve/server.py``): the handler's own spans,
``http.read`` (reading and parsing the request until it is submitted) plus
``http.write`` (encoding and writing the answer), mean per answered
request, in ms: the part of ``http.self_ms`` the program times itself.
Nothing is read from a program without these spans."""
NAME = "http.handler_ms"

SPANS = ("http.read", "http.write")


def read(ctx: dict):
    by_id = {t["trace_id"]: t for t in ctx["traces"]}
    times = []
    for r in ctx["requests"]:
        trace = by_id.get(r["trace_id"])
        if not r["answered"] or trace is None:
            continue
        times.append(sum(s["t1"] - s["t0"] for s in trace["spans"]
                         if s["name"] in SPANS and s["t1"] is not None))
    found = any(s["name"] in SPANS for t in ctx["traces"]
                for s in t["spans"])
    return 1e3 * sum(times) / len(times) if times and found else None

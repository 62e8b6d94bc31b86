"""The share of a trial's life that no span of the worker names: 100 * (1 -
its depth-0 spans, cut to [`started`, `stopped`], over `stopped - started`),
the mean over the trials that completed inside the window. `started` is the
trial row's reservation (after `propose`), `stopped` its completion: what is
left is the template's construction and `destroy()`, the feedback to the
advisor and the row's writes."""


def read(result, cell, peaks):
    shares = []
    for t in result.get("trials", []):
        if t["status"] != "COMPLETED" or not t["stopped"] \
                or not result["t0"] <= t["stopped"] < result["t1"]:
            continue
        life = t["stopped"] - t["started"]
        spanned = sum(
            max(min(s["end"], t["stopped"]) - max(s["start"], t["started"]),
                0.0) for s in t["spans"] if s.get("depth", 0) == 0)
        if life > 0 and t["spans"]:
            shares.append(1.0 - spanned / life)
    return 100.0 * sum(shares) / len(shares) if shares else None

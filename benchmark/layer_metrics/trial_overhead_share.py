"""The share of the window the train worker spent outside its epochs:
100 * (1 - sum of the logged `epoch_time` inside the window / window)."""


def read(result, cell, peaks):
    window = result["t1"] - result["t0"]
    inside = 0.0
    for t in result.get("trials", []):
        for e in t["epochs"]:
            start, end = e["time"] - e["epoch_time"], e["time"]
            inside += max(min(end, result["t1"]) - max(start, result["t0"]),
                          0.0)
    if not inside:
        return None
    return 100.0 * (1.0 - inside / window)

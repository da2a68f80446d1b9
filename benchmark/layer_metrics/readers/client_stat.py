"""A statistic of the window as the client saw it (``stats.serve_metrics``),
steadier than the tail it stands beside: host clock, whole window."""


def read(reading, key):
    return reading.get("client", {}).get(key)

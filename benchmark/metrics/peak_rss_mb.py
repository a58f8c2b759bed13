"""Peak resident set of the process (getrusage), at the end of the run."""


def read(ev):
    return ev["rss_mb"]

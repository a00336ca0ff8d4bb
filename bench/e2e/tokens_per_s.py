"""Output tokens of the requests answered in the window over the window's
length; the window ends with the request in flight at ``--seconds``, so all
the work and all the time count."""


def read(run):
    toks = sum(r["T"] for r in run["records"] if r.get("ok"))
    return toks / run["window_s"] if run["window_s"] > 0 else None

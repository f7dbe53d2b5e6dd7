"""engine: share of the token rows the slot steps' matmuls ran that carried a
token (`sched_step_rows{what="valid"}` over `{what="run"}`, every kind, whole
window, tracing off).  A mixed step gives each of its slots `t` rows and a
decoding slot fills one of them; since PR 42 the step runs the valid rows
packed into 64 rows where they fit, and this is how well the rows that ran
were filled.  Reads nothing on a program without the counter."""


def read(ctx):
    a = ctx["after"].get("sched_step_rows")
    b = ctx["before"].get("sched_step_rows") or {}
    if not a:
        return None
    d = {k: v - b.get(k, 0) for k, v in a.items()}
    run = sum(v for k, v in d.items() if k.split("/")[0] == "run")
    valid = sum(v for k, v in d.items() if k.split("/")[0] == "valid")
    return 100.0 * valid / run if run else None

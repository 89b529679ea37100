"""What the served path adds to the job itself: the client's
`firstcontact_s` minus the job's own wall in its artifact
(`serve.job_wall_s`): HTTP, spool, queue, owner pipe, polling."""


def read(run):
    wall = (run["out"]["artifacts"]["job"].get("serve") or {}).get(
        "job_wall_s")
    return None if wall is None else \
        run["out"]["values"]["firstcontact_s"] - wall

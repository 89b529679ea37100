"""Per cent of the window's jobs answered by a warm engine
(`serve.warm_engine` in the artifact): the schedule `edit, edit, edit,
rerun` makes it 25."""

import served


def read(run):
    js = served.jobs(run)
    return 100.0 * sum(1 for j in js if j["serve"].get("warm_engine")) \
        / len(js) if js else None

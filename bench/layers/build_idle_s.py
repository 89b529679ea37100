"""Device-idle seconds per searched job while the owner is in a job's BUILD
spans: `load`, `parse`, `analyze`, `engine_build` and everything nested in
them (layout, bounds, arms, predicates) — every `jaxmc.*` span that is not
search, checkpoint, level, tier or device init
(bench/served.py, bench/spans.py)."""

import served


def read(run):
    return served.owner_idle_s(
        run, lambda name: name.startswith("jaxmc.")
        and not name.startswith(served.NOT_BUILD))

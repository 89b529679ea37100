"""As build_s.desk, from the served job's result artifact."""


def read(run):
    ph = {p["name"]: p["wall_s"]
          for p in run["out"]["artifacts"]["job"].get("phases", [])}
    return sum(ph.get(k, 0.0) for k in ("load", "parse", "engine_build"))

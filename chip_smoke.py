#!/usr/bin/env python3
r"""chip_smoke.py — the quickest proof that jaxmc still starts on the chip.

    python3 chip_smoke.py              # on a machine with a TPU

Drives the main path once through the entry points a user would call
(`python -m jaxmc check`, `python -m jaxmc.serve run` + the HTTP
protocol, and — with >= 4 devices — the sharded engine through
`python -m jaxmc check --devices 4`), checks every count, verdict and
trace against the corpus manifest pins (jaxmc/corpus.py: exact counts
confirmed by the exact interpreter, the repo's semantic reference), and
prints as its LAST stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as jax reported it inside the legs
(`jax.devices()[0].platform`, `.device_kind`, `len(jax.devices())`,
read back from each leg's --metrics-out artifact / job summary — never
scraped from stdout).

ONE PROCESS PER CHIP.  This parent NEVER imports jax: a process that
has touched jax holds the chip, and every leg below is a child that
needs it.  The legs run one after another; each child has exited — and
released the chip — before the next starts.

Legs.  The REAL rung is specs/transfer_scaled_4p.cfg (24,035,597
generated / 9,394,019 distinct states); it completes on one v5e chip
(PERF.md: resident cold 697 s, warm 225 s), but a cold process pays one
XLA compile of ~45-60 s per capacity growth, so beside the other legs
it does not fit this script's 1200 s limit.  Legs B and C therefore run
smaller rungs, and say so:

  A  default (level) engine, pinned rung (311,153 / 153,701) + a
     violating model whose counterexample trace must come back from the
     device path
  B  resident engine on the FLOOR rung specs/transfer_scaled_4p8.cfg
     (4,767,576 / 1,859,252), TWICE as two processes sharing one
     compile cache: the second must hit the persistent cache, find the
     capacity profile (zero growth recompiles) and compile for fewer
     seconds
  C  default (level) engine again, as a SECOND process over leg A's
     cache (the real rung dropped to the pinned rung): what `check
     --backend tpu` gives a user, traces kept, must hit the cache
  D  served path: daemon + device owner, cold / byte-identical warm /
     cfg-variant submissions, clean SIGTERM drain
  E  four chips, when >= 4 devices are visible: the sharded engine on
     the REAL rung in one process; with fewer the leg SAYS it did not
     run (a statement, not a pass)
  F  cfg SYMMETRY over a real group: the resident engine on
     specs/transfer_symmetry_5p3.cfg (five interchangeable processes,
     S5: 29,382 / 9,336 where the unreduced model has 545,822 states);
     the device must canonicalise in the SORTED form
     (compile/symmetry2.py) — an unreduced fallback is a failure
  G  a spec bounded by the cfg's CONSTRAINT alone: the resident engine
     on specs/transfer_retry_3p.cfg (a retry loop whose counter nothing
     in the spec bounds: 16,553 / 5,515, 3,219 rows fingerprinted and
     discarded); the device must judge the constraint itself (gauge
     `constraint.compiled` 1) — an interpreter fallback is a failure

Any leg that fails, times out, demotes, or reports a platform other
than the one asked for ends the smoke non-zero with no result line.
Times printed here are observations for the next reader, not metrics of
record.  The compile cache is wherever JAX_COMPILATION_CACHE_DIR says,
else `<checkout>/.jax_cache` — this script places nothing.

`--rehearse-on-cpu` runs the same plumbing at toy size on XLA:CPU (for
the tests, and before spending chip time).  It says so, prints no
result object, and proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the contract's wall limit is 1200 s, compilation included; the legs'
#: own timeouts add up to less so a hang fails HERE, with a leg named
_LEG_TIMEOUT_S = 600.0

#: leg -> (spec, cfg) under specs/, at the chip's size and at the CPU
#: rehearsal's.  Counts are NOT written here: they come from the corpus
#: manifest pins (jaxmc/corpus.py), the one place they are recorded.
RUNGS = {
    "chip": {
        "A": ("transfer_scaled.tla", "transfer_scaled.cfg"),
        "A_bad": ("portoy.tla", "portoy_bad.cfg"),
        "B": ("transfer_scaled.tla", "transfer_scaled_4p8.cfg"),
        "C": ("transfer_scaled.tla", "transfer_scaled.cfg"),
        "D": ("transfer_scaled.tla", "transfer_scaled.cfg"),
        "D_variant": ("batchtoy.tla", "batchtoy_b.cfg"),
        "E": ("transfer_scaled.tla", "transfer_scaled_4p.cfg"),
        "F": ("transfer_symmetry.tla", "transfer_symmetry_5p3.cfg"),
        "G": ("transfer_retry.tla", "transfer_retry_3p.cfg"),
    },
    "rehearsal": {
        "A": ("constoy.tla", "constoy.cfg"),
        "A_bad": ("portoy.tla", "portoy_bad.cfg"),
        "B": ("symtoy.tla", "symtoy.cfg"),
        "C": ("symtoy.tla", "symtoy.cfg"),
        "D": ("constoy.tla", "constoy.cfg"),
        "D_variant": ("batchtoy.tla", "batchtoy_b.cfg"),
        "E": ("symtoy_scaled.tla", "symtoy_scaled.cfg"),
        "F": ("transfer_symmetry.tla", "transfer_symmetry_3p4.cfg"),
        "G": ("transfer_retry.tla", "transfer_retry_2p.cfg"),
    },
}


class SmokeFailure(Exception):
    """A leg did not meet its contract; main() exits non-zero."""


def need(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    def __init__(self, rehearsal: bool, out_dir: str,
                 leg_timeout: float):
        self.rehearsal = rehearsal
        self.leg_timeout = leg_timeout
        self.platform = "cpu" if rehearsal else "tpu"
        self.rungs = RUNGS["rehearsal" if rehearsal else "chip"]
        self.out = out_dir
        self.device = None  # the first leg's device identity
        self.env = dict(os.environ, PYTHONPATH=HERE, JAXMC_LEDGER="off")

    # ------------------------------------------------------- plumbing
    def paths(self, leg: str):
        spec, cfg = self.rungs[leg]
        return (os.path.join(HERE, "specs", spec),
                os.path.join(HERE, "specs", cfg))

    def pin(self, leg: str):
        from jaxmc.corpus import case_for_cfg  # no jax behind it
        case = case_for_cfg(self.rungs[leg][1])
        need(case is not None,
             f"{self.rungs[leg][1]} has no manifest pin in corpus.py")
        return case

    def run_child(self, tag: str, argv, timeout: float, extra_env=None):
        """One child process to completion; returns (rc, stdout).  Its
        stdout/stderr land in <out>/<tag>.{out,err} so a failure can be
        read after the machine is gone."""
        so = os.path.join(self.out, f"{tag}.out")
        se = os.path.join(self.out, f"{tag}.err")
        t0 = time.time()
        with open(so, "w") as fo, open(se, "w") as fe:
            p = subprocess.Popen(argv, cwd=HERE, stdout=fo, stderr=fe,
                                 env=dict(self.env, **(extra_env or {})))
            try:
                rc = p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                raise SmokeFailure(
                    f"{tag}: timed out after {timeout:.0f}s "
                    f"(stderr tail: {_tail(se)})")
        say(f"  [{tag}] rc={rc} in {time.time() - t0:.1f}s")
        with open(so) as fh:
            return rc, fh.read()

    def check(self, tag: str, leg: str, extra, want_rc: int = 0,
              no_deadlock: bool = False, same_count: bool = True):
        """`python -m jaxmc check` on the leg's rung; returns (artifact,
        stdout) after asserting rc, device and engine facts."""
        spec, cfg = self.paths(leg)
        art = os.path.join(self.out, f"{tag}.json")
        argv = [sys.executable, "-m", "jaxmc", "check", spec, "--cfg", cfg,
                "--backend", self.platform, "--metrics-out", art] + extra
        if no_deadlock:
            argv.append("--no-deadlock")
        rc, out = self.run_child(tag, argv, self.leg_timeout)
        need(rc == want_rc,
             f"{tag}: exit {rc}, wanted {want_rc} (stderr tail: "
             f"{_tail(os.path.join(self.out, tag + '.err'))})")
        with open(art) as fh:
            a = json.load(fh)
        self.assert_device(tag, a, same_count)
        need(a["result"].get("finished_on") == self.platform,
             f"{tag}: finished on {a['result'].get('finished_on')!r}")
        need(a["gauges"].get("expand.mode") == "compiled",
             f"{tag}: expand.mode={a['gauges'].get('expand.mode')!r}")
        self.report(tag, a)
        return a, out

    def assert_device(self, tag: str, a, same_count: bool = True) -> None:
        env = a.get("env") or {}
        need(env.get("platform") == self.platform,
             f"{tag}: ran on platform {env.get('platform')!r}, not "
             f"{self.platform!r}")
        need(env.get("device_kind") and env.get("device_count"),
             f"{tag}: artifact names no device ({env})")
        need(not a["gauges"].get("device.demoted")
             and not a["counters"].get("device.demotions"),
             f"{tag}: the run DEMOTED off the device: "
             f"{a['gauges'].get('device.demoted')}")
        dev = {"platform": env["platform"], "kind": env["device_kind"],
               "count": env["device_count"]}
        if self.device is None:
            self.device = dev
        if not same_count:
            dev = dict(dev, count=self.device["count"])
        need(dev == self.device,
             f"{tag}: device {dev} differs from the first leg's "
             f"{self.device}")

    def report(self, tag: str, a) -> None:
        """The per-leg observation line(s): device, versions, engine,
        compiles, cache, memory."""
        env, c, g = a.get("env") or {}, a["counters"], a["gauges"]
        hbm = (a.get("prof") or {}).get("hbm") or {}
        say(f"  [{tag}] platform={env.get('platform')} "
            f"device_kind={env.get('device_kind')!r} "
            f"devices={env.get('device_count')} "
            f"jax={env.get('jax_version')} "
            f"jaxlib={env.get('jaxlib_version')} "
            f"libtpu={env.get('libtpu_version')}")
        say(f"  [{tag}] expand.mode={g.get('expand.mode')} "
            f"xla_compiles={c.get('compile.xla_compiles', 0)} "
            f"xla_compile_s="
            f"{c.get('compile.xla_compile_s', 0.0):.1f} (set-up) "
            f"persistent_cache_hits="
            f"{c.get('compile.persistent_cache_hits', 0)} "
            f"misses={c.get('compile.persistent_cache_misses', 0)} "
            f"cache={g.get('compile.persistent_cache_guard')!r} "
            f"profile={g.get('profile.status')!r}")
        say(f"  [{tag}] peak_bytes_in_use="
            f"{hbm.get('measured_peak_bytes', 'not reported')} "
            f"hbm_model_peak_bytes={hbm.get('peak_bytes')} "
            f"buffers={hbm.get('buffers')}")

    def assert_counts(self, tag: str, res, case) -> None:
        got = (res.get("generated"), res.get("distinct"))
        need(res.get("ok") and not res.get("truncated"),
             f"{tag}: not a completed clean run: {res}")
        need(got == (case.generated, case.distinct),
             f"{tag}: counts {got} != manifest pin "
             f"({case.generated}, {case.distinct})")
        say(f"  [{tag}] counts {got[0]} generated / {got[1]} distinct "
            f"== pin; diameter {res.get('diameter')}; "
            f"search wall {res.get('wall_s')}s (observation)")

    # ----------------------------------------------------------- legs
    def leg_a(self) -> None:
        say("leg A: default engine, pinned rung + a violating model")
        case = self.pin("A")
        a, _ = self.check("A_ok", "A", [], no_deadlock=case.no_deadlock)
        self.assert_counts("A_ok", a["result"], case)
        bad = self.pin("A_bad")
        need(bad.expect.startswith("violation:"),
             f"{self.rungs['A_bad'][1]} is not pinned as a violation")
        b, out = self.check("A_bad", "A_bad", [], want_rc=1,
                            no_deadlock=bad.no_deadlock)
        viol = b["result"].get("violation") or {}
        need(viol.get("kind") == bad.expect.split(":", 1)[1],
             f"A_bad: violation {viol} != pinned {bad.expect}")
        # a checker that cannot show a trace from the device path is
        # not up: the counterexample must be PRINTED, state by state
        need("State 1:" in out and "is violated" in out,
             f"A_bad: no counterexample trace on stdout: {out[-400:]!r}")
        say(f"  [A_bad] {viol.get('kind')} {viol.get('name')!r} with a "
            f"{out.count('State ')}-state trace printed")

    def leg_b(self) -> None:
        say(f"leg B: resident engine on {self.rungs['B'][1]}, cold then "
            f"warm (two processes, one cache)")
        case = self.pin("B")
        arts = []
        for tag in ("B_cold", "B_warm"):
            a, _ = self.check(tag, "B", ["--resident", "--no-trace"],
                              no_deadlock=case.no_deadlock)
            self.assert_counts(tag, a["result"], case)
            fresh = sum(1 for lv in a["levels"]
                        if lv.get("fresh_compile"))
            say(f"  [{tag}] dispatches that paid an XLA compile: {fresh}")
            arts.append((a, fresh))
        (cold, _), (warm, warm_fresh) = arts
        cc, wc = cold["counters"], warm["counters"]
        need(wc.get("compile.persistent_cache_hits", 0) > 0,
             "B_warm: the second process hit nothing in the persistent "
             f"cache ({warm['gauges'].get('compile.persistent_cache_guard')})")
        need(wc.get("profile.hits", 0) >= 1 and warm_fresh <= 1,
             f"B_warm: capacity profile "
             f"{warm['gauges'].get('profile.status')!r}, {warm_fresh} "
             f"compiling dispatches — growth recompiles remain")
        need(wc.get("compile.xla_compile_s", 0.0)
             < cc.get("compile.xla_compile_s", 0.0),
             f"B_warm: compile seconds did not fall "
             f"({cc.get('compile.xla_compile_s')} -> "
             f"{wc.get('compile.xla_compile_s')})")

    def leg_c(self) -> None:
        say(f"leg C: default (level) engine on {self.rungs['C'][1]}, a "
            f"second process over leg A's cache (the real rung does not "
            f"fit the time limit beside leg B cold)")
        case = self.pin("C")
        a, _ = self.check("C_level", "C", [],
                          no_deadlock=case.no_deadlock)
        self.assert_counts("C_level", a["result"], case)
        need(a["counters"].get("compile.persistent_cache_hits", 0) > 0,
             "C_level: the level engine's second process hit nothing in "
             "the persistent cache")

    def leg_d(self) -> None:
        say("leg D: served path (daemon + device owner)")
        from jaxmc.serve.protocol import ServeClient  # no jax behind it
        spool = os.path.join(self.out, "spool")
        se = os.path.join(self.out, "D_daemon.err")
        opts = {"backend": "jax", "platform": self.platform}
        with open(se, "w") as fe:
            daemon = subprocess.Popen(
                [sys.executable, "-m", "jaxmc.serve", "run", "--spool",
                 spool, "--workers", "1"], cwd=HERE,
                stdout=subprocess.DEVNULL, stderr=fe, env=self.env)
        try:
            client = self._await_daemon(daemon, spool, ServeClient)
            spec, cfg = self.paths("D")
            vspec, vcfg = self.paths("D_variant")
            jobs = [("D_cold", spec, cfg, self.pin("D"), False),
                    ("D_warm", spec, cfg, self.pin("D"), True),
                    ("D_variant", vspec, vcfg, self.pin("D_variant"),
                     False)]
            for tag, sp, cf, case, want_warm in jobs:
                t0 = time.time()
                code, job = client.submit(sp, cf, opts)
                need(code == 200, f"{tag}: submit refused ({code}): {job}")
                try:
                    done = client.wait(job["id"], timeout=self.leg_timeout)
                except TimeoutError as ex:
                    raise SmokeFailure(f"{tag}: {ex}") from ex
                need(done.get("status") == "done",
                     f"{tag}: job ended {done.get('status')!r}: "
                     f"{done.get('error')}")
                code, summ = client.result(job["id"])
                need(code == 200, f"{tag}: no result artifact ({code})")
                say(f"  [{tag}] submit->verdict {time.time() - t0:.1f}s "
                    f"(observation)")
                self.assert_device(tag, summ)
                self.assert_counts(tag, summ["result"], case)
                sv = summ.get("serve") or {}
                need(bool(sv.get("warm_engine")) == want_warm,
                     f"{tag}: warm_engine={sv.get('warm_engine')!r}, "
                     f"wanted {want_warm}")
                need(summ["result"].get("finished_on") == "jax",
                     f"{tag}: finished on "
                     f"{summ['result'].get('finished_on')!r}")
                self.report(tag, summ)
            code, st = client.status()
            need(code == 200, f"D: /status answered {code}")
            need(st.get("device_owner_pid"),
                 "D: /status shows no device owner process")
            need(st.get("daemon_holds_device") is False,
                 "D: the DAEMON process holds a jax backend — it, not "
                 "its owner, would own the chip")
            need(st["counters"].get("serve.owner_respawns", 0) == 0,
                 f"D: owner respawned "
                 f"{st['counters'].get('serve.owner_respawns')}x")
            say(f"  [D] device_owner_pid={st['device_owner_pid']} "
                f"daemon_holds_device={st['daemon_holds_device']} "
                f"owner_respawns=0 jobs_done={st.get('jobs_done')}")
            daemon.send_signal(signal.SIGTERM)
            rc = daemon.wait(timeout=120)
            need(rc == 0, f"D: daemon exited {rc} on SIGTERM, not a "
                          f"clean drain (stderr tail: {_tail(se)})")
            say("  [D] SIGTERM -> clean drain (rc 0)")
        finally:
            if daemon.poll() is None:
                # a failed leg: SIGTERM first, so the daemon takes its
                # device owner down with it instead of orphaning it
                daemon.terminate()
                try:
                    daemon.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    daemon.kill()
                    daemon.wait()

    def _await_daemon(self, daemon, spool, client_cls):
        stamp = os.path.join(spool, "serve.json")
        deadline = time.time() + 60
        while time.time() < deadline:
            need(daemon.poll() is None,
                 f"D: daemon died at start-up (rc {daemon.returncode})")
            try:
                with open(stamp) as fh:
                    info = json.load(fh)
                if info.get("status") == "serving" and \
                        info.get("pid") == daemon.pid:
                    return client_cls(info["host"], info["port"])
            except (OSError, ValueError):
                pass
            time.sleep(0.2)
        raise SmokeFailure("D: daemon did not stamp its spool in 60s")

    def leg_e(self) -> None:
        # the device count is leg A's (this parent cannot ask jax);
        # run alone (--legs E), the child itself refuses < 4 devices
        n = self.device["count"] if self.device else None
        if n is not None and n < 4 and not self.rehearsal:
            say(f"leg E: mesh: not run ({n} device) — a statement, "
                f"not a pass")
            return
        say("leg E: sharded engine over four devices, one process "
            "(check --devices 4)")
        case = self.pin("E")
        # the normal path; in the rehearsal the session asks XLA:CPU for
        # its four host devices itself, so the count differs from leg A's
        a, _ = self.check("E_mesh", "E", ["--devices", "4"],
                          no_deadlock=case.no_deadlock,
                          same_count=not self.rehearsal)
        need(a["gauges"].get("mesh.devices") == 4,
             f"E_mesh: mesh.devices={a['gauges'].get('mesh.devices')!r}, "
             f"not the sharded engine over four")
        self.assert_counts("E_mesh", a["result"], case)
        peaks = a["gauges"].get("mesh.device_peak_bytes")
        if self.rehearsal:
            return  # XLA:CPU reports no per-device memory
        need(peaks and len(peaks) == 4,
             f"E_mesh: no per-device memory_stats ({peaks})")
        mean = sum(peaks) / len(peaks)
        say(f"  [E_mesh] per-device peak_bytes_in_use={peaks} "
            f"max/mean={max(peaks) / mean:.2f}")
        need(max(peaks) <= 2.0 * mean,
             f"E_mesh: one device holds {max(peaks)} bytes, more than "
             f"2x the mean {mean:.0f} — the tables were not sharded at "
             f"creation")


    def leg_f(self) -> None:
        say(f"leg F: cfg SYMMETRY on the resident engine, "
            f"{self.rungs['F'][1]}: the sorted canonicaliser")
        case = self.pin("F")
        a, out = self.check("F_sym", "F", ["--resident", "--no-trace"],
                            no_deadlock=case.no_deadlock)
        g = a["gauges"]
        need(g.get("symmetry.form") == "sorted",
             f"F_sym: symmetry.form={g.get('symmetry.form')!r}, group "
             f"order {g.get('symmetry.group_order')!r}: the reduction "
             f"did not run on the device in the sorted form")
        need("SYMMETRY NOT applied" not in out,
             "F_sym: the run warns that SYMMETRY was not applied")
        self.assert_counts("F_sym", a["result"], case)
        need(a["counters"].get("search.canon_rows")
             == a["result"].get("generated"),
             f"F_sym: search.canon_rows="
             f"{a['counters'].get('search.canon_rows')!r}")
        say(f"  [F_sym] symmetry.form=sorted group_order="
            f"{g.get('symmetry.group_order')}")

    def leg_g(self) -> None:
        say(f"leg G: a cfg CONSTRAINT on the resident engine, "
            f"{self.rungs['G'][1]}: judged on the device")
        case = self.pin("G")
        a, _ = self.check("G_con", "G", ["--resident", "--no-trace"],
                          no_deadlock=case.no_deadlock)
        g, c = a["gauges"], a["counters"]
        need(g.get("constraint.compiled") == 1
             and g.get("expand.constraints_interp") == 0,
             f"G_con: constraint.compiled="
             f"{g.get('constraint.compiled')!r}, "
             f"expand.constraints_interp="
             f"{g.get('expand.constraints_interp')!r}: the constraint "
             f"is not judged on the device")
        self.assert_counts("G_con", a["result"], case)
        need(c.get("search.rows_discarded", 0) > 0
             and c.get("search.slots_constrained", 0) > 0,
             f"G_con: search.rows_discarded="
             f"{c.get('search.rows_discarded')!r}, "
             f"search.slots_constrained="
             f"{c.get('search.slots_constrained')!r}")
        say(f"  [G_con] constraint.compiled=1 rows_discarded="
            f"{c['search.rows_discarded']} slots_constrained="
            f"{c['search.slots_constrained']}")


def _tail(path: str, n: int = 600) -> str:
    try:
        with open(path, errors="replace") as fh:
            return fh.read()[-n:].strip()
    except OSError:
        return "<no output>"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy-size plumbing rehearsal on XLA:CPU — NOT "
                         "a chip run, prints no result object")
    ap.add_argument("--legs", default="A,B,C,D,E,F,G",
                    help="comma-separated subset (debugging one leg; "
                         "a partial run prints no result object)")
    ap.add_argument("--leg-timeout", type=float, default=_LEG_TIMEOUT_S,
                    help="seconds one child may run (raise it to watch "
                         "a slow leg finish while debugging)")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="where leg artifacts, logs and the serve spool land")
    args = ap.parse_args(argv)
    need_files = ("jaxmc", "specs")
    if not all(os.path.isdir(os.path.join(HERE, d)) for d in need_files):
        print("chip_smoke: the jaxmc checkout is not here — nothing to "
              "drive", file=sys.stderr)
        return 2
    legs = [x.strip().upper() for x in args.legs.split(",") if x.strip()]
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    smoke = Smoke(args.rehearse_on_cpu, args.out, args.leg_timeout)
    if smoke.rehearsal:
        say("chip_smoke: REHEARSAL on XLA:CPU at toy size — this is "
            "NOT a chip run")
    t0 = time.time()
    try:
        for leg in legs:
            getattr(smoke, f"leg_{leg.lower()}")()
    except SmokeFailure as ex:
        print(f"chip_smoke: FAILED after {time.time() - t0:.0f}s: {ex}",
              file=sys.stderr)
        return 1
    say(f"chip_smoke: legs {','.join(legs)} passed in "
        f"{time.time() - t0:.0f}s; compile cache: "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or os.path.join(HERE, '.jax_cache')}")
    if smoke.rehearsal:
        say("chip_smoke: rehearsal passed — NOT a chip run, no result")
        return 0
    if legs != ["A", "B", "C", "D", "E", "F", "G"]:
        say("chip_smoke: partial run — no result")
        return 0
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

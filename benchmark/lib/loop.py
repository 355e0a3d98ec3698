"""One run: serve, warm up, measure, judge.

The system under test is a ``SchedulerServer`` started in this process
the way ``python -m kai_scheduler_tpu serve`` starts it (a snapshot
document loaded into a ``Cluster``, ``Scheduler(SchedulerConfig(...))``
with the options the configuration file states, port 0), spoken to over
HTTP on 127.0.0.1 by one client that waits for each reply.  One
iteration posts the cycle's churn (``/cluster/delta``, ``/intake``) and
then ``POST /cycle/stored``.  Nothing is judged inside the window:
documents and commits are kept, and the host model replays them once the
window has closed.
"""
from __future__ import annotations

import json
import os
import shutil
import time
import urllib.error

from . import host_model, meters, registry, trace_reduce

SPANS = ("churn_post", "cycle_post")


class Run:
    def __init__(self, config: dict, mix: dict, seed: int, root: str,
                 nodes: int | None = None):
        # the configuration names its cluster generator, the mix its
        # churn generator and its warm-up rule: each a file found by name
        self.gen = registry.module("generators",
                                   config["cluster"]["generator"])
        self.churn_gen = registry.module("churn", mix["churn"])
        self.warm = registry.module("warmup", mix["warmup"]["until"])
        self.spec = self.gen.scaled(config["cluster"], nodes)
        self.mix = self.churn_gen.scaled(
            mix, self.spec["nodes"] / config["cluster"]["nodes"])
        self.options = config["scheduler"]["options"]
        self.actions = config["scheduler"]["actions"]
        self.seed, self.root = seed, root
        self.cycles: list[dict] = []     # one sample per window cycle
        #: (delta, intake, commit) of every cycle, as the bytes that went
        #: over the wire: kept for the replay, and out of the garbage
        #: collector's way (the server's pauses are its own, not ours)
        self.records: list[tuple] = []
        self.submitted: dict = {}        # gang -> start of its intake POST
        self.failed = 0
        self.setup: dict = {}
        self.window: dict = {}
        self.trace: dict | None = None
        self.server = None

    # -- set-up ------------------------------------------------------------

    def start(self, meter: meters.CompileMeter) -> None:
        from kai_scheduler_tpu.framework.scheduler import (Scheduler,
                                                           SchedulerConfig)
        from kai_scheduler_tpu.framework.server import SchedulerServer
        from kai_scheduler_tpu.runtime.snapshot import load_cluster

        self.meter = meter
        t0 = time.perf_counter()
        cluster = self.gen.cluster_doc(self.spec, self.seed)
        t1 = time.perf_counter()
        scheduler = Scheduler(SchedulerConfig(**self.options))
        if list(scheduler.config.actions) != self.actions:
            raise ValueError(f"the configuration states {self.actions}, the "
                             f"program runs {scheduler.config.actions}")
        self.server = SchedulerServer(
            load_cluster(cluster), scheduler, port=0).start()
        self.http = meters.Client(self.server.port)
        self.churn = self.churn_gen.Churn(self.gen, self.spec, self.mix,
                                          cluster, self.seed)
        self.cluster_json = json.dumps(cluster)
        #: where set-up goes, seconds
        self.marks = {"cluster_doc_s": t1 - t0,
                      "server_start_s": time.perf_counter() - t1}

    def stop(self) -> None:
        if self.server is not None:
            self.server.stop()
            self.server = None

    def warm_up(self) -> None:
        """The window's own iteration, untimed, until the mix's rule
        says the run is steady."""
        rule = self.mix["warmup"]
        log = []
        for n in range(1, rule["max_cycles"] + 1):
            before = (self.http.jit_misses(),
                      self.meter.read()["compile_requests"])
            sample = self.iteration()
            after = (self.http.jit_misses(),
                     self.meter.read()["compile_requests"])
            log.append({"cycle": n, "wall_s": sample["iter_s"],
                        "jit_misses": after[0] - before[0],
                        "compile_requests": after[1] - before[1],
                        "binds": sample["binds"],
                        "evictions": sample["evictions"]})
            if self.warm.done(log, rule):
                break
        else:
            raise RuntimeError(f"no steady cycle in warm-up: {log}")
        self.setup = {"warmup_cycles": log, **self.marks,
                      **self.meter.read()}

    # -- the loop ----------------------------------------------------------

    def iteration(self, health: bool = False) -> dict:
        import jax.profiler as prof
        delta, intake = self.churn.documents()
        submitted = [g["name"] for g in intake["pod_groups_upsert"]]
        delta, intake = json.dumps(delta).encode(), json.dumps(intake).encode()
        raw = b'{"bind_requests": [], "evictions": []}'
        t0 = t1 = time.perf_counter()
        try:
            with prof.TraceAnnotation(SPANS[0]):
                self.http.post("/cluster/delta", delta)
                out = json.loads(self.http.post("/intake", intake))
            ok = out["shed"] == 0 and out["accepted"] == out["total"]
            t1 = time.perf_counter()
            with prof.TraceAnnotation(SPANS[1]):
                raw = self.http.post("/cycle/stored", b"{}")
        except urllib.error.HTTPError:
            # another status than 200: the cycle failed, the loop goes on
            ok = False
        t2 = time.perf_counter()
        commit = json.loads(raw)
        self.submitted.update((g, t0) for g in submitted)
        bound = {self.churn.gang_of[b["pod"]]
                 for b in commit["bind_requests"]}
        self.churn.observe(commit)
        self.records.append((delta, intake, raw))
        sample = {"t0": t0, "churn_post_s": t1 - t0, "cycle_post_s": t2 - t1,
                  "iter_s": t2 - t0, "ok": ok,
                  "binds": len(commit["bind_requests"]),
                  "evictions": len(commit["evictions"]),
                  "bind_wait_s": [t2 - self.submitted[g] for g in sorted(bound)
                                  if g in self.submitted]}
        if health:
            sample["health"] = self.http.get("/healthz")["last_cycle"]
        return sample

    def measure(self, seconds: float, trace: bool,
                max_cycles: int | None = None) -> None:
        import jax
        import jax.profiler as prof
        trace_dir = os.path.join(self.root, ".bench_out", "trace")
        n_trace = self.mix["trace_cycles"] if trace else 0
        misses0 = self.http.jit_misses()
        meter0 = self.meter.read()
        start = time.perf_counter()
        self.setup["window_start"] = start
        if n_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = prof.ProfileOptions()
            opts.python_tracer_level = 0
            prof.start_trace(trace_dir, profiler_options=opts)
        while True:
            sample = self.iteration(health=trace)
            self.cycles.append(sample)
            self.failed += not sample["ok"]
            if n_trace and len(self.cycles) == n_trace:
                prof.stop_trace()
            if len(self.cycles) >= n_trace and (
                    time.perf_counter() - start >= seconds
                    or len(self.cycles) == max_cycles):
                break
        end = time.perf_counter()
        meter1 = self.meter.read()
        self.window = {
            "seconds": end - start,
            "jit_misses": self.http.jit_misses() - misses0,
            "compile_requests": (meter1["compile_requests"]
                                 - meter0["compile_requests"]),
            "intake": self.http.get("/healthz")["intake"],
        }
        # what the server holds once the window has closed: pod -> node,
        # from its own snapshot document (a pending bind request is where
        # a bound pod's node is kept until a binder reports it running)
        stored = self.http.get("/snapshot")
        holds = {b["pod_name"]: b["selected_node"]
                 for b in stored["bind_requests"] if b["phase"] == "Pending"}
        holds.update((p["name"], p["node"]) for p in stored["pods"]
                     if p["node"])
        self.window["stored_nodes"] = holds
        # what the fullest chip held at its peak.  The TPU runtime books
        # live arrays and loaded programs as "in use" and a running
        # program's temporaries as "reserved"; the two are disjoint, and
        # the chip's largest free block is the limit less both
        held = [meters.memory_held(d.memory_stats() or {})
                for d in jax.local_devices()]
        self.window["memory_peak_bytes"] = max(sum(h) for h in held)
        self.window["memory_in_use_and_reserved"] = max(
            held, key=sum)
        if n_trace:
            on_cpu = jax.local_devices()[0].platform == "cpu"
            self.trace = trace_reduce.reduce(trace_reduce.load_xplane(
                trace_reduce.find_xplane(trace_dir), SPANS,
                rehearsal=on_cpu))
            shutil.rmtree(trace_dir, ignore_errors=True)

    # -- the verdict -------------------------------------------------------

    def judge(self) -> dict:
        """Replay everything posted and committed through the plain
        reference.  Returns each number compared beside its limit."""
        model = host_model.HostModel(json.loads(self.cluster_json))
        tallies = []
        for delta, intake, commit in self.records:
            model.apply_doc(json.loads(delta))
            model.apply_doc(json.loads(intake))
            tallies.append(model.check_commit(json.loads(commit)))
        checks = model.checks()
        checks["nodes_over_allocatable_recount"] = {
            "value": model.recount_over(), "limit": 0}
        checks["readback_mismatch"] = {
            "value": model.readback_mismatch(self.window["stored_nodes"]),
            "limit": 0}
        intake = self.window["intake"]
        checks["intake_refused"] = {
            "value": intake["shed"] + intake["rejected"]
            + intake["apply_errors"], "limit": 0}
        checks["cycles_failed"] = {"value": self.failed, "limit": 0}
        self.tallies = tallies[-len(self.cycles):]
        return checks

    @property
    def shapes(self) -> dict:
        """The logical sizes one cycle's solve works on."""
        shapes = self.gen.shapes(self.spec)
        shapes["gangs"] += self.churn.arriving_gangs
        return shapes

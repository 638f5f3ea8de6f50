"""Serving metrics — the replica's observability face.

Registered into the process-wide ``metrics_system()`` so they surface
through every existing sink: the ``/jmx`` endpoint of the replica's own
HTTP server, file sinks, and the periodic publisher. Source name
``serving.engine`` mirrors the ``namenode.ops`` convention.
"""

from __future__ import annotations

from hadoop_tpu.metrics import metrics_system

SOURCE = "serving.engine"


class ServingMetrics:
    """Queue depth / batch occupancy / TTFT / tokens/s / KV-pool usage.

    - ``queue_depth``        requests waiting for a slot or pages
    - ``batch_occupancy``    running requests in the fixed decode batch
    - ``kv_blocks_in_use``   allocated KV pages (and a 0..1 utilization)
    - ``time_to_first_token`` quantiles (s), submit → first token
    - ``decode_step``        per-step latency rate (num_ops = steps)
    - ``steps_run_ahead``    steps dispatched while the step before them
                             had not been read back yet (the serving
                             thread keeps one step in flight; against
                             the step count, the share of steps whose
                             host work ran under the device's)
    - ``steps_argmax_only`` / ``steps_topk``  steps whose sampler was an
                             arg-max and nothing else (no live lane had
                             a temperature), and steps in which a top-k
                             threshold was searched for (a live sampled
                             lane had ``top_k > 0``) — counted by the
                             step itself on the device, read with its
                             bundle
    - ``tokens_out``         generated tokens (monotonic; tokens/s is the
                             derivative any sink can take)
    - ``requests`` / ``preemptions`` lifetime counters
    - ``prefix_cache_hit_rate``  fraction of admitted prompt tokens
                             served from cached KV blocks (0..1)
    - ``prefix_cached_blocks``   resident reusable KV pages
    - ``prefix_tokens_reused`` / ``prefix_cache_evictions`` counters
    - ``chunk_occupancy``    fraction of the per-step prefill-chunk
                             budget actually used last step
    - ``prefill_backlog``    prompt tokens still awaiting prefill across
                             admitted requests (the stall gauge: how far
                             first tokens lag behind admission)
    - ``kv_hits_{hbm,host,dfs}`` per-tier KV block hit counters
    - ``kv_demotions`` / ``kv_promotions`` / ``kv_dfs_persists``
                             tier traffic (HBM→host spills, cold-tier
                             re-injections, DFS write-pipeline persists)
    - ``kv_fetch_seconds{tier=host|dfs}`` log-bucketed cold-fetch
                             latency histograms (one prom family)
    - ``spec_proposed`` / ``spec_accepted`` speculative-decoding draft
                             token counters (proposal vs verifier)
    - ``spec_accept_len``    log-bucketed accepted-draft-length
                             histogram per speculating lane-step
    - ``attn_pages_read`` / ``attn_pages_dense``  KV pages the steps'
                             live rows attended to, against the pages a
                             walk of every row's whole table would have
                             read; their ratio is the live-page share
    - ``kv_pages_live_steps`` / ``kv_pages_pool_steps``  pages of the
                             pool held at each step (by a lane or by the
                             prefix cache) against the pages it has,
                             both summed over steps: their ratio is the
                             pool's mean fill as the steps saw it
    - ``loop_passes``        (a stack run several times over one set of
                             weights) passes the steps ran — counted by
                             the step itself on the device; over the
                             step count it is the passes a token takes
    - ``attn_entries_live`` / ``attn_entries_selected``  (sparse
                             selection) context entries the steps' live
                             rows could attend to, against the entries
                             they kept (at most ``index_topk`` a row)
    - ``attn_pages_distinct``  distinct KV pages under those rows,
                             counted from below (requests sharing a
                             radix chain counted by the longest)
    - ``moe_assignments`` / ``moe_assignments_local`` /
      ``moe_local_experts_hit``  (a replica holding a share of a wider
                             router) row-to-expert assignments of the
                             steps' live rows over the expert layers,
                             those that fell on experts held here, and
                             held experts with at least one (summed over
                             layers and steps; the last two counted on
                             the device, read with the step's bundle)
    - ``moe_expert_rows_max``  rows of the busiest held expert, summed
                             over layers and steps (device): times the
                             experts held over ``moe_assignments_local``
                             it is the load imbalance, 1.0 for an even
                             router
    - ``recurrent_state_restores`` / ``recurrent_state_cold_starts``
                             (a family with per-lane state) lanes started
                             from the state tail of a cached page — a
                             prefix hit, a resume after preemption —
                             against lanes started from nothing
    - ``qos_admitted`` / ``qos_shed``  door QoS gate outcomes (sheds
                             are 429 + Retry-After responses)
    - ``qos_tenants``        tenants tracked by the decay scheduler
    - ``longctx_requests`` / ``longctx_blocks_streamed`` /
      ``longctx_window_fetches`` / ``longctx_chips`` /
      ``longctx_prefill_seconds``  long-context plane: prompts routed
                             to CP prefill, KV blocks streamed to the
                             cold tiers, decode window page-ins, CP
                             width, prefill wall time
    - ``phase_seconds{phase=engine.wait|…|engine.publish}`` cumulative
                             seconds of the scheduler thread in each
                             phase of its loop (one prom family,
                             ``htpu_serving_engine_phase_seconds_total``;
                             the phases tile an iteration, so their
                             rates sum to ~1 and say where the host's
                             time between device steps goes)
    - ``iteration_seconds``  histogram of the time between the starts
                             of two consecutive device steps of a busy
                             engine (waiting for work and compiles left
                             out) — a stall of the loop or of the whole
                             process shows here, where ``decode_step``
                             cannot see it; the second kind is measured
                             and named by ``process_stalled_seconds``
    - ``process_stalls`` / ``process_stalled_seconds`` /
      ``process_stalled_seconds{cause=suspended|gc|throttled|
      cpu_starved|memory|io|gil|frozen|unknown}``  freezes of the whole
                             process as ``util.misc.PauseMonitor`` saw
                             them (a tick of its sleeper later than the
                             threshold), their seconds, and the seconds
                             by what the record says froze it (one prom
                             family, ``htpu_serving_engine_process_
                             stalled_seconds_total``); 0 in a sound run
    - ``process_tick_oversleep_seconds``  histogram of how late every
                             tick of that sleeper woke: the threshold
                             is chosen over its tail
    - ``process_gc_seconds`` / ``engine_thread_run_delay_seconds`` /
      ``process_cpu_throttled_seconds`` /
      ``process_pressure_seconds{resource=cpu|memory|io}`` /
      ``process_major_faults`` / ``host_steal_seconds``  what the
                             monitor reads every tick, stalled or not,
                             so that a run that is slow THROUGHOUT shows
                             too: seconds inside full collections; the
                             scheduler thread runnable with no CPU; the
                             cgroup's CPU quota throttling it; some task
                             of the host stalled on the resource
                             (``/proc/pressure`` ``some``); faults that
                             went to the disk; the hypervisor's steal
                             (mean of one CPU). A counter whose source
                             the machine lacks stays 0
    - ``ttft_stage_seconds{stage=queue|prefill_wait|prefill}``  the
                             three intervals that sum to each request's
                             time to first token: submitted → admitted
                             → first chunk dispatched → first token
    - ``weight_bytes``       measured resident model weight bytes
                             (``htpu_weight_bytes`` on ``/prom`` — the
                             weight-plane capacity signal: int8 resident
                             weights shrink it ~4x and the KV budget
                             grows by exactly the difference)
    """

    def __init__(self, source: str = SOURCE):
        reg = metrics_system().source(source)
        self.registry = reg
        self.queue_depth = reg.gauge(
            "queue_depth", "requests waiting for admission")
        self.batch_occupancy = reg.gauge(
            "batch_occupancy", "running requests in the decode batch")
        self.kv_blocks_in_use = reg.gauge(
            "kv_blocks_in_use", "allocated KV-cache pages")
        self.kv_block_utilization = reg.gauge(
            "kv_block_utilization", "fraction of the KV pool in use")
        self.ttft = reg.quantiles(
            "time_to_first_token", "submit to first token, seconds")
        # log-bucketed twins for the /prom exposition (quantiles/rates
        # stay for JMX parity — same samples, two shapes)
        self.ttft_hist = reg.histogram(
            "time_to_first_token_seconds", "submit to first token")
        self.decode_step = reg.rate(
            "decode_step", "one continuous-batching decode step")
        self.decode_step_hist = reg.histogram(
            "decode_step_seconds", "one continuous-batching decode step")
        self.steps_run_ahead = reg.counter(
            "steps_run_ahead",
            "steps dispatched before the step ahead of them was read")
        self.steps_argmax_only = reg.counter(
            "steps_argmax_only",
            "steps in which no live lane sampled: arg-max only")
        self.steps_topk = reg.counter(
            "steps_topk",
            "steps in which a top-k threshold was searched for")
        # the scheduler loop's own time, one family with the bounded
        # label set of engine.PHASES (inline literals: the label lint
        # proves the bound), and the TTFT timeline's three stages
        self.phase_seconds = {
            ph: reg.counter(
                f"phase_seconds_{ph}",
                "scheduler-thread seconds by phase of the engine loop",
                prom_name="serving_engine_phase_seconds",
                prom_labels={"phase": ph})
            for ph in ("engine.wait", "engine.admit", "engine.propose",
                       "engine.pages", "engine.dispatch",
                       "engine.readback", "engine.deliver",
                       "engine.publish")}
        self.iteration_hist = reg.histogram(
            "iteration_seconds",
            "start of one device step to the next, of a busy engine")
        # the process under the loop (util.misc.PauseMonitor, started by
        # DecodeEngine.start): stalls by cause, every tick's oversleep,
        # and what the OS and the collector charge the process tick by
        # tick. The label sets are those of misc.CAUSES and of
        # /proc/pressure, inline for the label lint.
        self.process_stalls = reg.counter(
            "process_stalls", "freezes of the whole process")
        self.process_stalled_seconds = reg.counter(
            "process_stalled_seconds", "seconds the whole process froze")
        self.process_stalled_by_cause = {
            cause: reg.counter(
                f"process_stalled_seconds_{cause}",
                "seconds the whole process froze, by what froze it",
                prom_name="serving_engine_process_stalled_seconds",
                prom_labels={"cause": cause})
            for cause in ("suspended", "gc", "throttled", "cpu_starved",
                          "memory", "io", "gil", "frozen", "unknown")}
        self.process_tick_oversleep_hist = reg.histogram(
            "process_tick_oversleep_seconds",
            "how late each tick of the pause monitor's sleeper woke")
        # (the monitor's key for the gain, the counter it feeds)
        self._process_ticks = [
            ("gc_s", reg.counter(
                "process_gc_seconds", "seconds inside full collections")),
            ("engine_run_delay_s", reg.counter(
                "engine_thread_run_delay_seconds",
                "scheduler thread runnable and given no CPU")),
            ("throttled_s", reg.counter(
                "process_cpu_throttled_seconds",
                "the cgroup's CPU quota throttled the process")),
            ("major_faults", reg.counter(
                "process_major_faults", "page faults that went to disk")),
            ("steal_s", reg.counter(
                "host_steal_seconds",
                "the hypervisor's steal, mean of one CPU")),
        ] + [(f"{res}_some_s", reg.counter(
            f"process_pressure_seconds_{res}",
            "some task of the host stalled on the resource",
            prom_name="serving_engine_process_pressure_seconds",
            prom_labels={"resource": res}))
            for res in ("cpu", "memory", "io")]
        self.ttft_stage_hist = {
            stage: reg.histogram(
                f"ttft_stage_seconds_{stage}",
                "time to first token by stage of the request's timeline",
                prom_name="serving_engine_ttft_stage_seconds",
                prom_labels={"stage": stage})
            for stage in ("queue", "prefill_wait", "prefill")}
        self.tokens_out = reg.counter(
            "tokens_out", "tokens generated (all requests)")
        self.requests = reg.counter("requests", "requests submitted")
        self.preemptions = reg.counter(
            "preemptions", "requests evicted from the KV pool")
        self.prefix_cache_hit_rate = reg.gauge(
            "prefix_cache_hit_rate",
            "fraction of prompt tokens served from cached KV blocks")
        self.prefix_cached_blocks = reg.gauge(
            "prefix_cached_blocks", "resident reusable KV pages")
        self.prefix_tokens_reused = reg.counter(
            "prefix_tokens_reused",
            "prompt tokens whose prefill was skipped via the prefix cache")
        self.prefix_cache_evictions = reg.counter(
            "prefix_cache_evictions",
            "cached KV pages evicted (LRU) to feed live allocations")
        self.chunk_occupancy = reg.gauge(
            "chunk_occupancy",
            "fraction of the per-step prefill chunk budget used")
        self.prefill_backlog = reg.gauge(
            "prefill_backlog",
            "prompt tokens still awaiting prefill across admitted "
            "requests")
        # tiered KV cache: per-tier hit counters, demotion/promotion
        # traffic, and log-bucketed fetch latency published under ONE
        # prom family (kv_fetch_seconds{tier=...}) — a dashboard reads
        # the HBM→host→DFS waterfall off a single query
        self.kv_hits_hbm = reg.counter(
            "kv_hits_hbm", "KV blocks served from the HBM radix tier")
        self.kv_hits_host = reg.counter(
            "kv_hits_host",
            "KV blocks recovered from the host-RAM ring")
        self.kv_hits_dfs = reg.counter(
            "kv_hits_dfs",
            "KV blocks recovered from the DFS prefix store")
        self.kv_demotions = reg.counter(
            "kv_demotions",
            "zero-ref KV pages spilled HBM -> host ring at eviction")
        self.kv_promotions = reg.counter(
            "kv_promotions",
            "KV pages re-injected into HBM from a cold tier")
        self.kv_dfs_persists = reg.counter(
            "kv_dfs_persists",
            "KV pages persisted to the DFS prefix store")
        self.kv_fetch_hist = {
            tier: reg.histogram(
                f"kv_fetch_seconds_{tier}",
                "cold-tier KV block fetch latency",
                prom_name="kv_fetch_seconds",
                prom_labels={"tier": tier})
            for tier in ("host", "dfs")}
        # speculative decoding: draft tokens proposed by the n-gram
        # index vs accepted by the in-step verifier, plus a
        # log-bucketed per-lane accepted-length histogram (one prom
        # family — the acceptance-depth distribution in one query)
        self.spec_proposed = reg.counter(
            "spec_proposed",
            "draft tokens proposed to the speculation lane")
        self.spec_accepted = reg.counter(
            "spec_accepted",
            "draft tokens accepted by the in-step verifier")
        self.spec_accept_len = reg.histogram(
            "spec_accept_len",
            "accepted draft-prefix length per speculating lane-step")
        # paged attention: how far its traffic follows the live contexts
        # (counted on the host from the scheduler's mirrors, per step)
        self.attn_pages_read = reg.counter(
            "attn_pages_read",
            "KV pages the steps' live rows attended to")
        self.attn_pages_dense = reg.counter(
            "attn_pages_dense",
            "KV pages a walk of every row's whole block table would read")
        self.kv_pages_live_steps = reg.counter(
            "kv_pages_live_steps",
            "KV pages held (lanes and prefix cache), summed over steps")
        self.kv_pages_pool_steps = reg.counter(
            "kv_pages_pool_steps",
            "KV pages the pool has, summed over steps")
        # a stack run several times over one set of weights (zero for
        # families without a pass loop)
        self.loop_passes = reg.counter(
            "loop_passes",
            "passes over the layer stack, summed over steps (device's)")
        # sparse selection and the held share of a wider router (zero
        # for families without them)
        self.attn_entries_live = reg.counter(
            "attn_entries_live",
            "context entries the steps' live rows could attend to")
        self.attn_entries_selected = reg.counter(
            "attn_entries_selected",
            "context entries the sparse selection kept for those rows")
        self.attn_pages_distinct = reg.counter(
            "attn_pages_distinct",
            "distinct KV pages under the steps' live rows, from below")
        self.moe_assignments = reg.counter(
            "moe_assignments",
            "row-to-expert assignments of live rows, all expert layers")
        self.moe_assignments_local = reg.counter(
            "moe_assignments_local",
            "those assignments that fell on experts held by this replica")
        self.moe_local_experts_hit = reg.counter(
            "moe_local_experts_hit",
            "held experts with an assignment, summed over layers and steps")
        self.moe_expert_rows_max = reg.counter(
            "moe_expert_rows_max",
            "rows of the busiest held expert, summed over layers and steps")
        self.recurrent_state_restores = reg.counter(
            "recurrent_state_restores",
            "lanes started from the state tail of a cached page")
        self.recurrent_state_cold_starts = reg.counter(
            "recurrent_state_cold_starts",
            "lanes with per-lane state started from nothing")
        # door QoS: admissions vs sheds (429) and tracked tenants — the
        # autoscaler scrapes qos_shed off /prom as a scale-out signal
        # (a shedding fleet is past its SLO by definition)
        self.qos_admitted = reg.counter(
            "qos_admitted", "requests admitted through the QoS gate")
        self.qos_shed = reg.counter(
            "qos_shed",
            "requests shed (429 + Retry-After) at the serving door")
        self.qos_tenants = reg.gauge(
            "qos_tenants", "tenants tracked by the decay cost scheduler")
        # fleet SLO scoreboard (obs/slo): class-labeled request
        # accounting the doctor diffs per poll window. The class set
        # is the BOUNDED p0..p3 ladder (DecayCostScheduler level,
        # clamped — see hadoop_tpu.obs.slo.SLO_CLASSES); the tuples
        # stay inline literals so the label lint can prove the bound.
        self.slo_ttft_hist = {
            cls: reg.histogram(
                f"slo_ttft_seconds_{cls}",
                "submit to first token by tenant class",
                prom_name="slo_ttft_seconds",
                prom_labels={"class": cls})
            for cls in ("p0", "p1", "p2", "p3")}
        self.slo_token_hist = {
            cls: reg.histogram(
                f"slo_token_seconds_{cls}",
                "per-token decode seconds by tenant class",
                prom_name="slo_token_seconds",
                prom_labels={"class": cls})
            for cls in ("p0", "p1", "p2", "p3")}
        self.slo_requests = {
            (cls, outcome): reg.counter(
                f"slo_requests_{cls}_{outcome}",
                "door outcomes by tenant class",
                prom_name="slo_requests",
                prom_labels={"class": cls, "outcome": outcome})
            for cls in ("p0", "p1", "p2", "p3")
            for outcome in ("ok", "shed", "failed")}
        # the weight plane: measured resident weight bytes (int8
        # payloads + scale planes under serving.parity=relaxed, plain
        # dtype bytes bitwise) — the number the KV budget subtracts
        self.weight_bytes = reg.gauge(
            "weight_bytes", "resident model weight bytes on the chip")
        # the long-context plane (serving/longctx): monster prompts
        # routed to CP prefill, KV blocks streamed into the cold
        # tiers, decode window page-ins, CP width, and the prefill
        # wall-time histogram (htpu_longctx_* on /prom)
        self.longctx_requests = reg.counter(
            "longctx_requests",
            "prompts routed to the long-context CP prefill plane")
        self.longctx_blocks_streamed = reg.counter(
            "longctx_blocks_streamed",
            "prefilled KV blocks streamed into the cold tiers")
        self.longctx_window_fetches = reg.counter(
            "longctx_window_fetches",
            "decode working-set window page-ins (per layer, window)")
        self.longctx_chips = reg.gauge(
            "longctx_chips", "context-parallel width of the mesh")
        self.longctx_prefill_hist = reg.histogram(
            "longctx_prefill_seconds",
            "context-parallel prefill wall time per prompt")

    # util.misc.PauseMonitor's sink (two ServingMetrics over one
    # registry hold the same counters: the monitor feeds them as one)

    def process_tick(self, oversleep_s: float, gains: dict) -> None:
        self.process_tick_oversleep_hist.add(max(0.0, oversleep_s))
        for key, counter in self._process_ticks:
            if gains.get(key):
                counter.incr(gains[key])

    def process_stall(self, record: dict) -> None:
        self.process_stalls.incr()
        self.process_stalled_seconds.incr(record["seconds"])
        self.process_stalled_by_cause[record["cause"]].incr(
            record["seconds"])

    def snapshot(self):
        return self.registry.snapshot()

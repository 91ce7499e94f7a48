"""The infer-server role: request coalescing, the scan-stacked dispatch,
params off the learner channel, heartbeats, chaos, lifecycle.

One ROUTER at ``comms.infer_port`` multiplexes every remote-policy
actor's requests:

* ``("infer", msg)`` from actors — ``msg`` carries one half-group's
  stacked observations, its epsilon ladder slice, the RAW per-step key
  (as uint32 key data), and the group id.  The server replies
  ``("act", {...})`` with that group's actions and acting-time Q-values,
  stamped with the param version and learner epoch they were computed
  under.  A request decoded while the server has no params yet gets
  ``("dry", {"rid": ...})`` so the client falls back immediately instead
  of waiting out ``infer_wait_s``.

Adaptive batching: the first decoded request opens a window; the server
keeps draining the socket until ``infer_batch_max`` requests are queued
or ``infer_window_ms`` elapsed, then groups same-shaped requests and
runs each group as ONE ``lax.scan`` over the stacked requests — the
scan-of-identical-bodies batching PR 2 pinned bit-identical for the
learner's fused steps, applied to acting.  The scan length pads to
pow2-quantized widths (repeating the last request; padded outputs are
discarded) so the compile count stays bounded no matter how request
counts fluctuate.

Bit-parity: each scan step computes exactly
``policy_fn(params, obs, eps, fold_in(key, group))`` — the same program
the actor's local ``_grouped_policy`` runs — so remote actions/Q are
bit-identical to local acting for the same params and key chain
(tests/test_infer.py pins it; it is what makes the local fallback a pure
scheduling event).

Params ride the EXISTING param channel: the server subscribes like any
actor (SUB + CONFLATE, latest-wins) — no new publish cycle — and with
``comms.infer_device_params`` keeps them device-placed on arrival (the
device-to-device path on a shared-device deployment; skipped on the CPU
backend like the ingest pipeline's staging ring).  Replies carry the
subscriber's ``learner_epoch`` so clients can discard a dead life's
stragglers (PR 8 fencing).

Membership: ordinary :class:`~apex_tpu.fleet.heartbeat.Heartbeat`\\ s
(role ``"infer"``) ship to the learner's chunk port, so the
:class:`~apex_tpu.fleet.registry.FleetRegistry`, ``--role status``, the
chaos drills, and the supervisor all work on this role for free; the
beats carry the serving gauges (queue depth, batch-size p50/p90,
coalesce latency) the status table and Prometheus exposition surface.

Chaos: ``CHAOS_SEED``/``CHAOS_SPEC`` gate a per-identity plan under
``infer-<server_id>`` — ``kill`` fires on the request index
(``os._exit(137)``), ``drop_frac`` drops requests unanswered (the client
times out and falls back — exactly what a dying server produces), and
``mute`` swallows outgoing replies while ingress stays up.
"""

from __future__ import annotations

import time

import numpy as np

from apex_tpu.config import ApexConfig, CommsConfig
from apex_tpu.obs import spans as obs_spans
from apex_tpu.obs.spans import LatencyHistogram
from apex_tpu.runtime import wire
from apex_tpu.serving import fence
from apex_tpu.tenancy import namespace as tenancy_ns


def quantize_pow2(n: int, cap: int) -> int:
    """Scan length for ``n`` queued requests: the next power of two, capped
    (same discipline as the ingest pipeline's scan-shortfall widths — a
    bounded set of compiled lengths, never one per request count)."""
    n = max(1, min(int(n), int(cap)))
    p = 1
    while p < n:
        p *= 2
    return min(p, int(cap))


def make_batched_policy(policy_fn):
    """Jit ``policy_fn`` as a scan over stacked requests.  Each scan step
    re-wraps its request's raw key data and folds in its group id INSIDE
    the compiled program — element for element the actor-local
    ``_grouped_policy`` computation, so remote results are bit-identical
    to local acting (the scan-of-identical-bodies contract from the
    learner's scan_fused_steps)."""
    import jax

    # nb: the name must not collide with any host-side method in this
    # module — apexlint's jit-scope detection is name-based by design
    def _scan_requests(params, obs, eps, key_data, groups):
        def body(carry, xs):
            o, e, kd, g = xs
            key = jax.random.fold_in(jax.random.wrap_key_data(kd), g)
            return carry, policy_fn(params, o, e, key)

        _, (actions, q) = jax.lax.scan(body, 0, (obs, eps, key_data,
                                                 groups))
        return actions, q

    return jax.jit(_scan_requests)


class _RequestChaos:
    """The infer-server fault gate: one RNG draw per decoded request off
    the seeded per-identity stream (:mod:`apex_tpu.fleet.chaos`), so the
    server's kills and drops replay exactly, run after run."""

    def __init__(self, plan):
        self.plan = plan
        self._rng = plan.rng() if plan is not None else None
        self._n = 0
        self.dropped = 0

    def on_request(self) -> str:
        """"ok" | "drop"; a scheduled kill never returns."""
        if self.plan is None:
            return "ok"
        i = self._n
        self._n += 1
        if self.plan.kill_at is not None and i >= self.plan.kill_at:
            from apex_tpu.fleet.chaos import _die
            _die(self.plan.identity, i)
        if self._rng.random() < self.plan.drop_frac:
            self.dropped += 1
            return "drop"
        return "ok"


class InferServer:
    """Socket loop around one jitted policy (module docstring).
    Single-threaded on purpose: one thread owns the ROUTER, the param
    subscriber, and the dispatch order — the same thread-affinity
    contract the replay shards keep (and apexlint J013 now enforces)."""

    def __init__(self, comms: CommsConfig, policy_fn, server_id: int = 0,
                 bind_ip: str = "*", heartbeat: bool = True, sub=None,
                 port: int | None = None):
        import zmq

        from apex_tpu.fleet.chaos import chaos_from_env

        self._zmq = zmq
        self.comms = comms
        self.server_id = int(server_id)
        self.identity = f"infer-{server_id}"
        self.batched = make_batched_policy(policy_fn)
        self.port = int(port) if port is not None else comms.infer_port
        self.sock = zmq.Context.instance().socket(zmq.ROUTER)
        self.sock.bind(f"tcp://{bind_ip}:{self.port}")
        # params: latest-wins off the learner channel (``sub``), or
        # injected via set_params (tests/bench drive the server without a
        # learner).  Device placement is flag-gated and CPU-exempt.
        self.sub = sub
        self.params = None
        self.param_version = 0
        self.learner_epoch = 0
        self._place = bool(comms.infer_device_params)
        # serving-tier version gate (apex_tpu/serving/deploy drives it
        # over the ctl channel): while ``_pin`` holds a model fence,
        # installs BEYOND it are held (counted) and the shard keeps
        # serving what it has; ``_incumbent`` retains the pre-canary
        # params so a rollback restores them bit-identically.
        self._pin: tuple | None = None
        self._incumbent: tuple | None = None    # (version, params, epoch)
        self.held = 0                   # installs refused by the pin
        self.gate_rollbacks = 0         # incumbent restores taken
        self.ctl_cmds = 0
        # tenant entries (PR 13): each non-default tenant served here
        # gets its OWN params/version/epoch/compiled-policy/subscriber —
        # requests coalesce per (tenant, shape), so one tenant's batch
        # never runs under another's params.  The default tenant stays
        # on the attributes above, bit-identical to the single-tenant
        # server; the serve-ctl version gate also governs only the
        # default tenant (per-tenant canaries are a ROADMAP follow-up).
        self.tenants: dict[str, dict] = {}
        self.unknown_tenant = 0
        # serving counters / gauges (heartbeats + stats())
        self.requests = 0
        self.replies = 0
        self.dry_replies = 0            # requests answered before params
        self.rejected = 0               # payloads outside the allowlist
        self.dispatches = 0
        self.batch_hist = LatencyHistogram()      # requests per dispatch
        self.coalesce_hist = LatencyHistogram()   # recv -> dispatch, s
        self._queue_depth = 0
        chaos = chaos_from_env()
        plan = chaos.plan_for(self.identity) if chaos is not None else None
        self.chaos = _RequestChaos(plan)
        self._mute = bool(plan is not None and plan.mute_replies)
        self.chaos_muted = 0
        self._hb = None
        self._hb_sender = None
        if heartbeat:
            from apex_tpu.fleet.heartbeat import HeartbeatEmitter
            from apex_tpu.runtime.transport import ChunkSender
            self._hb_sender = ChunkSender(comms, self.identity)
            self._hb = HeartbeatEmitter(
                self.identity, role="infer",
                interval_s=comms.heartbeat_interval_s,
                counters_fn=lambda: {"chunks_sent": self.replies,
                                     "acks_received": self.requests},
                gauges_fn=self.gauges)

    # -- params --------------------------------------------------------------

    def set_params(self, version: int, params, epoch: int = 0) -> None:
        """Install params directly (tests, bench, co-located trainers);
        the serving path is identical to subscriber-fed params.  The
        epoch-fenced gate applies HERE — pinned shards hold (count)
        installs beyond the fence, so subscriber and direct installs
        obey one deployment discipline."""
        eff_epoch = int(epoch) if epoch else self.learner_epoch
        if self._pin is not None and fence.beyond(eff_epoch, version,
                                                  self._pin):
            self.held += 1
            return
        self.params = self._placed(params)
        self.param_version = int(version)
        if epoch:
            self.learner_epoch = int(epoch)

    # -- the serving-tier ctl channel (apex_tpu/serving/deploy) -------------

    def apply_ctl(self, body: dict) -> dict:
        """One deployment-controller command, applied on the socket
        thread (the gate and the dispatch order can never race).  All
        commands are idempotent — the controller RECONCILES every tick,
        so a respawned shard re-converges without special casing.

        * ``freeze``: stash current params (once) and pin at the
          shard's OWN current fence — the steady-state verb: the tier
          serves frozen, judged models, never the raw stream.
        * ``pin``: hold installs beyond an explicit (epoch, version)
          fence.
        * ``canary``: stash current params as the incumbent (once) and
          track the live stream.
        * ``rollback``: restore the stashed incumbent bit-identically
          and pin at ITS fence; a shard with no stash serving beyond
          the given fence (a respawn that picked up the candidate)
          drops to dry replies — clients fall back to local acting,
          never act on the rejected model.
        * ``promote``: clear pin + stash — the gate opens so the tier
          takes the newly judged version off the stream (the
          controller re-freezes next tick).
        * ``status`` (or anything else): report state only.
        """
        cmd = body.get("cmd")
        self.ctl_cmds += 1
        f = None
        if "epoch" in body or "version" in body:
            f = fence.fence_key(body.get("epoch"), body.get("version"))
        if cmd == "freeze":
            if self.params is not None and self._incumbent is None:
                self._incumbent = (self.param_version, self.params,
                                   self.learner_epoch)
            self._pin = fence.fence_key(self.learner_epoch,
                                        self.param_version)
        elif cmd == "pin" and f is not None:
            self._pin = f
        elif cmd == "canary":
            if self._incumbent is None and self.params is not None:
                self._incumbent = (self.param_version, self.params,
                                   self.learner_epoch)
            self._pin = None
        elif cmd == "rollback":
            if self._incumbent is not None:
                v, p, e = self._incumbent
                if fence.beyond(self.learner_epoch, self.param_version,
                                (e, v)):
                    self.gate_rollbacks += 1    # the restore changed
                self.params, self.param_version = p, int(v)  # something
                self.learner_epoch = int(e)
                self._incumbent = None
                self._pin = fence.fence_key(e, v)
            elif self._pin is not None and fence.at_or_before(
                    self.learner_epoch, self.param_version, self._pin):
                pass        # already rolled back / frozen pre-candidate
            elif f is not None and self.params is not None \
                    and fence.beyond(self.learner_epoch,
                                     self.param_version, f):
                # a respawned shard serving the candidate with no stash:
                # serving it would violate the rollback — serve dry
                # (clients act locally, bit-identically) until the next
                # promotion opens the gate
                self.params = None
                self._pin = f
            elif f is not None and self._pin is None:
                self._pin = f
        elif cmd == "promote":
            self._pin = None
            self._incumbent = None
        return self.ctl_state(rid=body.get("rid"))

    def ctl_state(self, rid=None) -> dict:
        """Gate state for ctl replies and stats(): plain builtins."""
        out = {"shard": self.server_id,
               "epoch": self.learner_epoch,
               "version": self.param_version,
               "pinned": self._pin is not None,
               "pin": list(self._pin) if self._pin is not None else None,
               "held": self.held,
               "rollbacks": self.gate_rollbacks,
               "has_incumbent": self._incumbent is not None,
               "has_params": self.params is not None}
        if rid is not None:
            out["rid"] = rid
        return out

    def _placed(self, params):
        if not self._place:
            return params
        import jax
        if jax.default_backend() == "cpu":
            return params           # host arrays ARE the device arrays
        return jax.device_put(params)

    # -- tenants (PR 13) -----------------------------------------------------

    def add_tenant(self, tenant: str, policy_fn, sub=None) -> None:
        """Serve one more tenant from this shard: its own compiled
        policy (its env's model — obs geometry and action count differ
        per tenant) and, optionally, a subscriber on ITS learner's
        param channel.  Direct installs come via
        :meth:`set_tenant_params` (tests, co-located trainers)."""
        if tenancy_ns.is_default(tenant):
            return                  # the default tenant IS the server
        self.tenants[tenant] = {
            "batched": make_batched_policy(policy_fn),
            "sub": sub, "params": None, "version": 0, "epoch": 0}

    def set_tenant_params(self, tenant: str, version: int, params,
                          epoch: int = 0) -> None:
        entry = self.tenants[tenant]
        entry["params"] = self._placed(params)
        entry["version"] = int(version)
        if epoch:
            entry["epoch"] = int(epoch)

    def _poll_params(self) -> None:
        if self.sub is not None:
            got = self.sub.poll(0)
            if got is not None:
                version, params = got
                self.set_params(version, params,
                                epoch=getattr(self.sub, "learner_epoch",
                                              0))
        for tenant, entry in self.tenants.items():
            sub = entry["sub"]
            if sub is None:
                continue
            got = sub.poll(0)
            if got is not None:
                version, params = got
                self.set_tenant_params(
                    tenant, version, params,
                    epoch=getattr(sub, "learner_epoch", 0))

    # -- serving -------------------------------------------------------------

    def step(self, timeout_ms: int = 100) -> int:
        """One poll/coalesce/dispatch round; returns requests served."""
        self._poll_params()
        if self._hb is not None:
            hb = self._hb.maybe_beat(self.param_version)
            if hb is not None:
                self._hb_sender.send_stat(hb)
        if not self.sock.poll(timeout_ms, self._zmq.POLLIN):
            return 0
        pending = self._coalesce()
        if not pending:
            return 0
        served = 0
        for group in self._group_by_shape(pending):
            served += self._dispatch(group)
        return served

    def _coalesce(self) -> list:
        """Drain decoded requests until ``infer_batch_max`` are queued or
        ``infer_window_ms`` elapsed since the first — the adaptive batch
        window.  Returns ``[(ident, msg, recv_monotonic), ...]``."""
        deadline = None
        out: list = []
        while len(out) < self.comms.infer_batch_max:
            wait_ms = 0
            if deadline is not None:
                wait_ms = max(0, int((deadline - time.monotonic()) * 1000))
            if not self.sock.poll(wait_ms, self._zmq.POLLIN):
                break
            ident, payload = self.sock.recv_multipart()
            try:
                got = wire.restricted_loads(payload)
            except wire.WireRejected:
                self.rejected += 1      # counted, dropped, NO reply: a
                continue                # hostile payload costs its sender
            #                             one fallback wait, nobody else's
            if not (isinstance(got, tuple) and len(got) == 2
                    and isinstance(got[1], dict)):
                self.rejected += 1      # well-pickled garbage included
                continue
            if got[0] == "ctl":
                # deployment-controller command (apex_tpu/serving):
                # applied here on the one socket thread, outside the
                # batch window and the chaos request stream
                self._reply(ident, ("ctl_ok", self.apply_ctl(got[1])))
                continue
            if got[0] != "infer":
                self.rejected += 1
                continue
            if self.chaos.on_request() == "drop":
                continue                # unanswered: the client falls back
            msg = got[1]
            self.requests += 1
            obs_spans.stamp(msg, "infer_batch")
            out.append((ident, msg, time.monotonic()))
            if deadline is None:
                deadline = (time.monotonic()
                            + self.comms.infer_window_ms / 1000.0)
        self._queue_depth = len(out)
        return out

    @staticmethod
    def _group_by_shape(pending: list) -> list[list]:
        """Same-tenant, same-shaped requests share one scan dispatch (a
        scan needs one stacked geometry AND one params pytree: the
        tenant key is what guarantees one tenant's batch never runs
        under another's params).  A like-configured single-tenant fleet
        produces at most the two half-group widths, exactly as
        before."""
        by_key: dict[tuple, list] = {}
        for item in pending:
            tenant = str(item[1].get("tenant")
                         or tenancy_ns.DEFAULT_TENANT)
            by_key.setdefault((tenant, item[1]["obs"].shape),
                              []).append(item)
        return list(by_key.values())

    def _dry_group(self, group: list) -> int:
        """No params for this group's tenant yet: tell its clients to
        act locally NOW rather than letting them wait out
        infer_wait_s."""
        for ident, msg, _ in group:
            self.dry_replies += 1
            self._reply(ident, ("dry", {"rid": msg["rid"]}))
        return len(group)

    def _dispatch(self, group: list) -> int:
        """One scan-stacked device dispatch over ``group`` (same tenant
        + obs shape), padded to a pow2-quantized length by repeating the
        last request — each scan step depends only on its own inputs, so
        the padding changes compile count, never results."""
        tenant = str(group[0][1].get("tenant")
                     or tenancy_ns.DEFAULT_TENANT)
        if tenancy_ns.is_default(tenant):
            params, batched = self.params, self.batched
            pv, epoch = self.param_version, self.learner_epoch
        else:
            entry = self.tenants.get(tenant)
            if entry is None:
                self.unknown_tenant += 1    # unadmitted tenant: its
                return self._dry_group(group)   # clients act locally
            params, batched = entry["params"], entry["batched"]
            pv, epoch = entry["version"], entry["epoch"]
        if params is None:
            return self._dry_group(group)
        n = len(group)
        width = quantize_pow2(n, self.comms.infer_batch_max)
        idx = list(range(n)) + [n - 1] * (width - n)
        obs = np.stack([group[i][1]["obs"] for i in idx])
        eps = np.stack([np.asarray(group[i][1]["eps"], np.float32)
                        for i in idx])
        keys = np.stack([np.asarray(group[i][1]["key"]) for i in idx])
        groups = np.asarray([int(group[i][1]["group"]) for i in idx],
                            np.int32)
        actions, q = batched(params, obs, eps, keys, groups)
        actions, q = np.asarray(actions), np.asarray(q)
        self.dispatches += 1
        self.batch_hist.record(float(n))
        now = time.monotonic()
        for r, (ident, msg, t_recv) in enumerate(group):
            self.coalesce_hist.record(max(0.0, now - t_recv))
            reply = {"rid": msg["rid"], "actions": actions[r], "q": q[r],
                     "pv": pv, "epoch": epoch}
            spans = msg.get(obs_spans.SPAN_KEY)
            if spans:
                obs_spans.stamp_spans(spans, "infer_reply")
                reply[obs_spans.SPAN_KEY] = spans
            self.replies += 1
            self._reply(ident, ("act", reply))
        return n

    def _reply(self, ident: bytes, msg) -> None:
        if self._mute:
            self.chaos_muted += 1       # the reply dies on the down link
            return
        try:
            self.sock.send_multipart([ident, wire.dumps(msg)],
                                     self._zmq.DONTWAIT)
        except self._zmq.Again:
            pass        # a gone client's reply is droppable by contract

    # -- lifecycle / observability -------------------------------------------

    def run(self, stop_event=None, max_seconds: float | None = None) -> dict:
        deadline = (None if max_seconds is None
                    else time.monotonic() + max_seconds)
        while True:
            if stop_event is not None and stop_event.is_set():
                break
            if deadline is not None and time.monotonic() > deadline:
                break
            self.step()
        return self.stats()

    def gauges(self) -> dict:
        """The serving gauges heartbeats carry to the registry (status
        table + Prometheus exposition)."""
        import jax
        b, c = self.batch_hist.snapshot(), self.coalesce_hist.snapshot()
        # serve_* rows: the registry's per-shard pinned-version view —
        # the deployment controller's reconcile target is auditable from
        # `--role status` without a ctl round-trip
        return {"tenants": 1 + len(self.tenants),
                "backend_accel": float(jax.default_backend() != "cpu"),
                "queue_depth": self._queue_depth,
                "batch_p50": b["p50_s"], "batch_p90": b["p90_s"],
                "coalesce_ms_p50": round(c["p50_s"] * 1000.0, 3),
                "requests": self.requests, "replies": self.replies,
                "dry_replies": self.dry_replies,
                "rejected": self.rejected,
                "serve_epoch": self.learner_epoch,
                "serve_version": self.param_version,
                "serve_pinned": int(self._pin is not None),
                "serve_held": self.held,
                "serve_rollbacks": self.gate_rollbacks}

    def stats(self) -> dict:
        return {"server": self.server_id,
                "param_version": self.param_version,
                "learner_epoch": self.learner_epoch,
                "dispatches": self.dispatches,
                "chaos_dropped": self.chaos.dropped,
                "chaos_muted": self.chaos_muted,
                "ctl_cmds": self.ctl_cmds,
                **self.gauges()}

    def close(self) -> None:
        self.sock.close(linger=0)
        if self._hb_sender is not None:
            self._hb_sender.close(drain_s=0.0)
        if self.sub is not None:
            self.sub.close()
        for entry in self.tenants.values():
            if entry["sub"] is not None:
                entry["sub"].close()


def dqn_policy_fn(cfg: ApexConfig):
    """The policy program the server serves — the SAME builder the actor
    families jit locally (one function, two call sites: that identity is
    the whole bit-parity argument)."""
    from apex_tpu.models import make_q_network
    from apex_tpu.models.dueling import make_policy_fn
    from apex_tpu.training.apex import dqn_model_spec
    return make_policy_fn(make_q_network(dqn_model_spec(cfg)))


def run_infer_server(cfg: ApexConfig, family: str = "dqn",
                     server_id: int = 0, stop_event=None,
                     max_seconds: float | None = None,
                     bind_ip: str = "*") -> dict:
    """The ``--role infer`` entry point: build the jitted policy from the
    fleet config, subscribe the param channel, serve until stopped.
    Returns the final stats dict.  Skips the startup barrier like the
    replay shards — the server is useful the moment its ROUTER binds
    (actors fall back locally until it answers)."""
    from apex_tpu.obs.trace import get_ring, set_process_label
    from apex_tpu.runtime import transport
    from apex_tpu.serving.shard import shard_port

    if family != "dqn":
        raise NotImplementedError(
            f"the inference plane currently serves the dqn family only "
            f"(got {family!r}); aql/r2d2 actors stay on local policies — "
            f"see ROADMAP.md")
    n_shards = max(1, getattr(cfg.comms, "infer_shards", 1))
    if not 0 <= server_id < n_shards:
        raise ValueError(
            f"infer shard id {server_id} outside [0, {n_shards}) — set "
            f"--infer-shards/APEX_INFER_SHARDS fleet-wide")
    set_process_label(f"infer-{server_id}")
    get_ring()                      # arm the trace ring's dump triggers
    # explicit empty topic: the infer shard is SHARED-plane — its base
    # subscriber always serves the default tenant's channel, even if an
    # operator leaks APEX_TENANT into the server's environment
    sub = transport.ParamSubscriber(cfg.comms, topic=b"")
    server = InferServer(cfg.comms, dqn_policy_fn(cfg),
                         server_id=server_id, bind_ip=bind_ip, sub=sub,
                         port=shard_port(cfg.comms, server_id))
    # tenant entries (PR 13): one compiled policy + one param SUB per
    # roster tenant — the SUB connects that tenant's OWN learner
    # endpoint and subscribes its topic tag, so requests coalesced per
    # (tenant, group) always dispatch under the right tenant's params
    import dataclasses
    roster = tenancy_ns.load_roster()
    for tenant, spec in sorted(roster.items()):
        if spec.family != "dqn":
            print(f"infer-{server_id}: tenant {tenant!r} skipped "
                  f"(family {spec.family!r} unserved — ROADMAP.md)",
                  flush=True)
            continue
        tcfg = cfg.replace(env=dataclasses.replace(cfg.env,
                                                   env_id=spec.env_id))
        tsub = transport.ParamSubscriber(
            tenancy_ns.tenant_comms(cfg.comms, spec),
            topic=tenancy_ns.param_topic(tenant))
        server.add_tenant(tenant, dqn_policy_fn(tcfg), sub=tsub)
    print(f"infer-{server_id}: serving on port {server.port} "
          f"(shard {server_id}/{n_shards}, "
          f"tenants=1+{len(server.tenants)}, "
          f"batch_max={cfg.comms.infer_batch_max}, "
          f"window_ms={cfg.comms.infer_window_ms}, "
          f"device_params={cfg.comms.infer_device_params})", flush=True)
    try:
        return server.run(stop_event=stop_event, max_seconds=max_seconds)
    finally:
        server.close()

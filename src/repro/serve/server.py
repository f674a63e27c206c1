"""``BatchServer`` — population-as-ensemble inference in ONE jitted call.

The paper's training claim — vectorize the whole population and one
compiled call costs ~one member — applies unchanged to inference: requests
are padded to a fixed batch, broadcast across the member axis, and every
ensemble member's deterministic forward runs inside one jitted, donated
executable (``vmap`` over members, exactly like the training backends).
The reduction across members is part of the same program, so an ensemble
answer costs one dispatch, not ``k``:

  * ``mean`` — average the member actions (continuous); for discrete
    action spaces this is plurality weight, i.e. identical to ``vote``.
  * ``vote`` — majority vote over the members' greedy actions (discrete).
  * ``best`` — the single fittest member's action (the ensemble as a hot
    standby: promotion picks WHO is best, serving stays one program).

Population bigger than one device: pass an ``IslandLayout`` mesh and the
member axis is ``shard_map``'d over the ``"pop"`` axis — each island runs
its own member block's forward, the reduction is the only cross-island
collective, and the call is still one jitted program (the serving mirror
of the ``"islands"`` update backend).

Donation: the *request buffer* is donated (a request batch is consumed by
its answer — XLA reuses it for the output), never the params (they must
survive for the next request).  After warm-up a call moves no bytes
between host and device except the explicit request ingress/egress;
``tests/test_serve.py`` pins that with ``jax.transfer_guard``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.ensemble import ServingSet
from repro.serve.forward import PolicyForward
from repro.telemetry import LatencyWindow

MODES = ("mean", "vote", "best")


class BatchServer:
    """Pads/batches observation requests and answers them with the
    ensemble.

    ``forward`` is the shared :class:`PolicyForward`; ``spec`` the
    ``repro.envs`` EnvSpec (discrete-ness and action arity decide what the
    reductions mean); ``serving_set`` the initial
    :class:`~repro.serve.ensemble.ServingSet` (install more via
    :meth:`install` as the ``ContinuousEvaluator`` promotes).  A new set of
    the SAME ensemble size reuses the compiled executable; a different size
    recompiles once (promotions are control-plane rare).
    """

    def __init__(self, forward: PolicyForward, spec, serving_set=None, *,
                 max_batch: int = 256, mode: str = "mean", mesh=None,
                 donate: bool = True, telemetry=None,
                 telemetry_every: int = 100):
        if mode not in MODES:
            raise ValueError(f"unknown reduction mode {mode!r}; one of "
                             f"{MODES}")
        if mode == "vote" and not spec.discrete:
            raise ValueError(
                f"mode='vote' needs a discrete action space but env "
                f"{spec.name!r} is continuous; use 'mean' or 'best'")
        self.forward = forward
        self.spec = spec
        self.mode = mode
        self.max_batch = max_batch
        self.mesh = mesh
        self.set: ServingSet | None = None
        self._pending: list = []
        self.requests_served = 0
        # serving telemetry: per-request-batch latency histogram + batch
        # fill ratio + queue depth, summarized into one "serve" row every
        # ``telemetry_every`` served batches.  All host-side bookkeeping
        # around the jitted call — the hot path itself is untouched (the
        # transfer-guard test runs with a live sink attached).
        self.telemetry = telemetry
        self.telemetry_every = max(1, telemetry_every)
        self._window = LatencyWindow()
        self._recording = True

        members_fn = forward.members
        self._request_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            members_fn = jax.shard_map(
                forward.members, mesh=mesh,
                in_specs=(P("pop"), P()), out_specs=P("pop"),
                check_vma=False)
            # requests enter replicated over the mesh; placing them there
            # explicitly keeps the hot path free of implicit reshards
            self._request_sharding = NamedSharding(mesh, P())

        def infer(params, best, obs):
            acts = members_fn(params, obs)              # (M, B, ...)
            if mode == "best":
                return jnp.take(acts, best, axis=0)
            if spec.discrete:
                # mean == vote on a discrete space: plurality of the
                # members' greedy actions
                votes = jax.nn.one_hot(acts, spec.act_dim).sum(0)
                return jnp.argmax(votes, axis=-1).astype(acts.dtype)
            return acts.mean(0)

        self._infer = jax.jit(infer, donate_argnums=(2,) if donate else ())
        if serving_set is not None:
            self.install(serving_set)

    # ---------------------------------------------------------- promotion
    def install(self, serving_set: ServingSet):
        """Swap the ensemble (a ``ContinuousEvaluator`` promotion).  With
        an islands mesh the member axis must tile the islands, same rule as
        the training backend."""
        if self.mesh is not None:
            islands = self.mesh.shape["pop"]
            if serving_set.size % islands:
                raise ValueError(
                    f"serving set of {serving_set.size} members does not "
                    f"split over {islands} islands; pick an ensemble size "
                    f"the mesh tiles")
        self.set = serving_set
        self._params = self._place(serving_set.params)
        self._best = jnp.asarray(serving_set.best, jnp.int32)
        return self

    def _place(self, params):
        if self.mesh is None:
            return jax.device_put(params)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh, P("pop"))
        return jax.device_put(params, jax.tree.map(lambda _: sh, params))

    # ------------------------------------------------------------ serving
    def warmup(self):
        """Compile the ensemble executable before the first real request
        (one padded batch of zeros).  XLA warns when the donated request
        buffer can't alias the action output (obs_dim != act_dim — donation
        then just releases the buffer early instead of reusing it); that
        compile-time note is expected and silenced here so serving logs
        stay clean."""
        import warnings

        self._recording = False   # a compile is not a latency sample
        try:
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                self.serve(np.zeros((1, self.spec.obs_dim), np.float32))
        finally:
            self._recording = True
        return self

    def place_request(self, obs):
        """Explicit request ingress: a device-resident buffer with the
        executable's input sharding (replicated over the mesh on the
        islands path, plain placement otherwise).  This is the ONLY
        transfer a request pays — everything after it runs under
        ``transfer_guard('disallow')``."""
        if self._request_sharding is None:
            return jax.device_put(obs)
        return jax.device_put(obs, self._request_sharding)

    def infer_device(self, obs):
        """The raw jitted ensemble call on a device-resident padded batch
        — the no-host-round-trip hot path (and what the transfer-guard
        test exercises).  ``obs`` is donated."""
        if self.set is None:
            raise ValueError("no ServingSet installed: call "
                             "server.install(serving_set) first")
        return self._infer(self._params, self._best, obs)

    def serve(self, obs) -> np.ndarray:
        """Answer a batch of observation requests.  ``obs`` is (B, obs_dim)
        (or a single (obs_dim,) request); B beyond ``max_batch`` is served
        in ``max_batch`` tiles, everything smaller is zero-padded up to the
        fixed shape so ONE executable serves every load level."""
        obs = np.asarray(obs, np.float32)
        single = obs.ndim == 1
        if single:
            obs = obs[None]
        t0 = time.perf_counter()
        outs = []
        tiles = 0
        for i in range(0, len(obs), self.max_batch):
            chunk = obs[i:i + self.max_batch]
            padded = np.zeros((self.max_batch,) + obs.shape[1:], np.float32)
            padded[:len(chunk)] = chunk
            acts = self.infer_device(self.place_request(padded))
            outs.append(np.asarray(acts)[:len(chunk)])
            tiles += 1
        self.requests_served += len(obs)
        if self._recording:
            # fill = real requests / padded slots dispatched: 1.0 means the
            # executable's fixed batch is earning its keep, low fill means
            # latency is being spent on zero padding
            self._window.add(time.perf_counter() - t0,
                             fill=len(obs) / (tiles * self.max_batch),
                             requests=len(obs))
            if (self.telemetry is not None
                    and self._window.count >= self.telemetry_every):
                self.report_telemetry()
        out = np.concatenate(outs, axis=0)
        return out[0] if single else out

    def report_telemetry(self):
        """Emit the current latency window as one ``serve`` row (p50/p99,
        fill ratio, queue depth) and start a fresh window.  Called
        automatically every ``telemetry_every`` batches; call it once more
        at shutdown for the partial tail."""
        if self.telemetry is None or not self._window.count:
            return
        self.telemetry.record(
            "serve", mode=self.mode, ensemble=getattr(self.set, "size", 0),
            max_batch=self.max_batch, **self._window.summary())
        self._window.reset()

    # ------------------------------------------------- request accumulation
    def submit(self, obs) -> int:
        """Enqueue one observation request; returns its slot in the next
        :meth:`flush`.  The queue refuses to grow past ``max_batch`` — at
        that point the caller flushes (a full batch IS the flush signal in
        a real frontend)."""
        if len(self._pending) >= self.max_batch:
            raise ValueError(f"request queue full ({self.max_batch}); "
                             f"flush() first")
        self._pending.append(np.asarray(obs, np.float32))
        self._window.observe_queue(len(self._pending))
        return len(self._pending) - 1

    def flush(self) -> np.ndarray:
        """Serve every queued request as one padded batch -> (queued, ...)
        actions in submission order."""
        if not self._pending:
            return np.zeros((0,))
        batch = np.stack(self._pending)
        self._pending = []
        return self.serve(batch)

"""Data-parallel NN training, analog of heat/nn/data_parallel.py.

The reference's ``DataParallel`` (data_parallel.py:22) wraps a torch module
and registers per-parameter backward hooks that Allreduce gradients —
blocking (``_blocking_hook`` :220) or non-blocking with just-in-time Waits
(``_nonblocking_hook`` :240, ``_forward_hook`` :278) — plus a fixed shared
seed so every rank starts from identical parameters (:105-106, :299-311).

TPU-native inversion: parameters live REPLICATED on the mesh and the batch
is sharded along the mesh axis; the gradient of a mean loss then *is* the
cross-replica average, with XLA inserting (and overlapping) the psum in the
backward pass.  The blocking/non-blocking distinction, the per-layer hook
ordering, and the identical-initialization dance all disappear: one jit'd
train step is the whole protocol.  Any flax ``linen.Module`` (or a bare
``apply(params, x)`` function) can be wrapped.

Explicit gradient-reduction schedules (overlap layer, docs/overlap.md):
the implicit schedule above leaves the collective placement entirely to
XLA.  :func:`reduce_gradients` is the explicit alternative — local
per-device gradients reduced by hand-placed psums inside a
``shard_map`` body, in **byte-bounded buckets issued in reverse layer
order** (``HEAT_TPU_GRAD_BUCKET_MB``, default 4) so the collective for
the last layers' gradients — ready first in the backward pass — is in
flight while the first layers' backward still computes: the TPU-native
transcription of the reference's ``_nonblocking_hook`` per-layer
``Iallreduce`` pipeline (data_parallel.py:240).  On a hierarchical mesh
each bucket reduces in two stages — ICI ``'node'`` psum, then DCN
``'global'`` psum — through
:class:`~heat_tpu.parallel.HierarchicalCommunication`.
``blocking=True`` selects the single fused psum of the whole flat
gradient (the reference's ``_blocking_hook``, :220); both schedules sum
the same elements across the same participants and produce identical
updates.  :class:`DataParallel` selects a schedule per instance — pass a
:class:`~heat_tpu.optim.DataParallelOptimizer` (its ``blocking`` flag
routes fused-vs-bucketed) or ``grad_reduction=`` directly; a bare optax
transform keeps the implicit schedule.
"""

from __future__ import annotations

import os
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.dndarray import DNDarray
from ..parallel.comm import Communication, HierarchicalCommunication, sanitize_comm

__all__ = [
    "DataParallel",
    "DataParallelMultiGPU",
    "bucket_partition",
    "reduce_gradients",
]

#: default collective bucket size for the bucketed schedule, MiB
DEFAULT_GRAD_BUCKET_MB = 4.0


def _grad_bucket_bytes() -> int:
    return int(
        float(os.environ.get("HEAT_TPU_GRAD_BUCKET_MB", str(DEFAULT_GRAD_BUCKET_MB)))
        * 2**20
    )


def bucket_partition(
    leaves: Sequence, bucket_bytes: Optional[int]
) -> List[List[int]]:
    """Partition gradient leaves into collective buckets.

    Returns lists of leaf indices in **reverse layer order** (the order
    gradients become ready in the backward pass), each bucket bounded by
    ``bucket_bytes`` (``None`` = unbounded, i.e. the fused schedule) and
    containing a single dtype (buckets are concatenated into one buffer
    per collective, which cannot mix dtypes).  A leaf larger than the
    bound gets its own bucket — leaves are never split."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i in reversed(range(len(leaves))):
        leaf = leaves[i]
        nbytes = int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        over = bucket_bytes is not None and cur_bytes + nbytes > bucket_bytes
        if cur and (over or leaf.dtype != cur_dtype):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = leaf.dtype
    if cur:
        buckets.append(cur)
    return buckets


def reduce_gradients(
    grads: Any,
    comm: Optional[Communication] = None,
    blocking: bool = False,
    bucket_bytes: Optional[int] = None,
):
    """Cross-device mean of a local-gradient pytree — call INSIDE a
    ``shard_map`` body (it issues named-axis psums).

    ``blocking=False`` (default): one psum per byte-bounded bucket in
    reverse layer order, so XLA can overlap each bucket's collective
    with the remaining backward compute.  ``blocking=True``: a single
    fused psum of the whole flattened gradient (per dtype).  On a
    :class:`HierarchicalCommunication` each bucket reduces in two
    stages: psum over the ``'node'`` (ICI) axis, then over the
    ``'global'`` (DCN) axis.  Both schedules sum identical elements
    across identical participants, so the averaged gradients — and the
    optimizer updates they produce — are identical.

    The number of buckets issued is added to the shared overlap-stats
    counter ``grad_buckets`` at trace time."""
    from ..utils.overlap import _bump

    comm = sanitize_comm(comm)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    if blocking:
        buckets = bucket_partition(leaves, None)
    else:
        buckets = bucket_partition(
            leaves, _grad_bucket_bytes() if bucket_bytes is None else bucket_bytes
        )
    _bump("grad_buckets", len(buckets))
    hier = isinstance(comm, HierarchicalCommunication)
    inv = 1.0 / comm.size
    sizes = [int(l.size) for l in leaves]
    out: List[Any] = [None] * len(leaves)
    for bucket in buckets:
        flat = [jnp.ravel(leaves[i]) for i in bucket]
        buf = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
        if hier:
            # two-stage: node-local reduce rides ICI, then one smaller
            # cross-node reduce rides DCN (the reference's DDP-then-MPI
            # hierarchy, heat/optim/dp_optimizer.py:450)
            buf = comm.psum(buf, comm.node_axis)
            buf = comm.psum(buf, comm.global_axis)
        else:
            buf = comm.psum(buf)
        buf = buf * jnp.asarray(inv, buf.dtype)
        offset = 0
        for i in bucket:
            out[i] = jax.lax.slice(buf, (offset,), (offset + sizes[i],)).reshape(
                leaves[i].shape
            )
            offset += sizes[i]
    return jax.tree_util.tree_unflatten(treedef, out)


class DataParallel:
    """Distributed data-parallel wrapper (data_parallel.py:22).

    Parameters
    ----------
    module : flax.linen.Module or Callable
        A flax module, or an ``apply(params, x)`` function.
    comm : Communication, optional
        Mesh over which the batch is sharded (default: world).
    optimizer : optional
        An optax gradient transformation, or a
        :class:`~heat_tpu.optim.DataParallelOptimizer` wrapping one — the
        wrapper's ``blocking`` flag then selects the explicit gradient
        schedule (``True`` -> fused single psum, ``False`` -> bucketed
        overlapped psums).  Enables :meth:`step`.
    blocking_parameter_updates : bool
        ``True`` selects the explicit single fused gradient psum (the
        reference's ``_blocking_hook``, :220).  ``False`` (default)
        keeps the implicit schedule, where XLA places and overlaps the
        reduction itself (the compiler-native analog of the :240
        non-blocking pipeline).
    grad_reduction : str, optional
        Explicit schedule override: ``"implicit"`` (XLA-placed),
        ``"bucketed"`` (reverse-order byte-bounded psums, see
        :func:`reduce_gradients`) or ``"fused"`` (one flat psum).
        Unknown values raise.  Default: derived from ``optimizer`` /
        ``blocking_parameter_updates`` as above.
    """

    def __init__(
        self,
        module: Any,
        comm: Optional[Communication] = None,
        optimizer: Any = None,
        blocking_parameter_updates: bool = False,
        grad_reduction: Optional[str] = None,
    ):
        from ..optim.dp_optimizer import DataParallelOptimizer

        self.module = module
        self.comm = sanitize_comm(comm)
        self.blocking_parameter_updates = blocking_parameter_updates
        if isinstance(optimizer, DataParallelOptimizer):
            if grad_reduction is None:
                grad_reduction = optimizer.schedule
            optimizer = optimizer.optimizer
        if grad_reduction is None:
            grad_reduction = "fused" if blocking_parameter_updates else "implicit"
        if grad_reduction not in ("implicit", "bucketed", "fused"):
            raise ValueError(
                "grad_reduction must be 'implicit', 'bucketed' or 'fused', "
                f"got {grad_reduction!r}"
            )
        self.grad_reduction = grad_reduction
        self._optimizer = optimizer
        self._opt_state = None
        self.params = None
        self._apply = module.apply if hasattr(module, "apply") else module
        self._train_step = None
        self._train_step_explicit = None
        self._epoch_fn = None
        self._programs = {}

    # ------------------------------------------------------------------
    def init(self, key, sample_input) -> "DataParallel":
        """Initialize parameters, replicated on the mesh (the analog of the
        reference's shared-seed ``_reset_parameters``, :299)."""
        if isinstance(sample_input, DNDarray):
            sample_input = sample_input._dense()
        if hasattr(self.module, "init"):
            params = self.module.init(key, sample_input)
        else:
            raise TypeError("module has no .init; pass explicit params to set_params")
        self.set_params(params)
        return self

    def set_params(self, params) -> None:
        rep = NamedSharding(self.comm.mesh, P())
        self.params = jax.device_put(params, rep)
        if self._optimizer is not None:
            self._opt_state = jax.device_put(self._optimizer.init(self.params), rep)
        self._train_step = None
        self._train_step_explicit = None
        self._epoch_fn = None
        self._programs = {}

    # ------------------------------------------------------------------
    def _forward_params(self):
        """Parameter pytree used for inference (hook for subclasses)."""
        return self.params

    def __call__(self, x):
        """Forward pass on a (batch-sharded) input (data_parallel.py:150)."""
        if self.params is None:
            raise RuntimeError("call init() or set_params() first")
        wrap = isinstance(x, DNDarray)
        xd = x._dense() if wrap else x
        out = self._apply(self._forward_params(), xd)
        if wrap:
            return DNDarray.from_dense(out, x.split, x.device, x.comm)
        return out

    forward = __call__

    # ------------------------------------------------------------------
    def value_and_grad(self, loss_fn: Callable, x, y) -> Tuple[jnp.ndarray, Any]:
        """Loss and cross-replica-averaged parameter gradients.

        ``loss_fn(pred, target) -> scalar`` must reduce with a mean over the
        batch; the mean over the sharded batch axis is exactly the
        reference's Allreduce(SUM)/size per-layer hook (:220), emitted once
        by XLA instead of per tensor.
        """
        xd = x._dense() if isinstance(x, DNDarray) else x
        yd = y._dense() if isinstance(y, DNDarray) else y

        def total_loss(params):
            return loss_fn(self._apply(params, xd), yd)

        return jax.value_and_grad(total_loss)(self.params)

    @staticmethod
    def _loss_key(loss_fn: Callable):
        """``(key, pins)`` for a loss function: the code object plus the
        IDENTITY of every piece of captured state (closure cells, default
        args, a bound method's ``__self__``).  A fresh lambda per loop
        iteration capturing the same objects reuses the compiled program;
        a lambda capturing *different* state (``lambda p, t, w=w: ...``
        with a new ``w``) rebuilds instead of silently evaluating the old
        trace.  ``pins`` holds the exact objects whose ids appear in the
        key — the cache entry must keep it alive, because the function
        object alone pins its closure CELLS, not their historical
        contents: rebinding the enclosing variable frees the old contents
        and a later object at the recycled address would alias the stale
        key.  Callables without a code object (``functools.partial``, C
        callables) key on their own identity — recreate them per call and
        each call retraces.  Like ``jax.jit`` itself, IN-PLACE mutation of
        a captured object (``obj.w = 2.0`` behind a bound method) is not
        observable: traced state is baked at compile time; rebind a new
        function/object to change it."""
        fn = getattr(loss_fn, "__func__", loss_fn)
        code = getattr(fn, "__code__", None)
        if code is None:
            return (id(loss_fn),), (loss_fn,)

        cells = []
        for c in fn.__closure__ or ():
            try:
                cells.append(c.cell_contents)
            except ValueError:  # empty cell (e.g. unbound recursive name)
                cells.append(c)
        bound_self = getattr(loss_fn, "__self__", None)
        defaults = tuple(fn.__defaults__ or ())
        kwdefaults = sorted((fn.__kwdefaults__ or {}).items())
        key = (
            code,
            id(bound_self),
            tuple(id(d) for d in defaults),
            tuple((k, id(v)) for k, v in kwdefaults),
            tuple(id(c) for c in cells),
        )
        pins = (loss_fn, bound_self, defaults, tuple(v for _, v in kwdefaults), tuple(cells))
        return key, pins

    _PROGRAM_CACHE_SIZE = 8

    def _cached_program(self, cache: dict, loss_fn: Callable, build: Callable):
        """Shared keyed-FIFO program cache (``_build`` and the
        hierarchical ``step``): returns ``build()``'s value, cached under
        :meth:`_loss_key` with the key's referent objects pinned for the
        entry's lifetime."""
        key, pins = self._loss_key(loss_fn)
        cached = cache.get(key)
        if cached is not None:
            return cached[0]
        value = build()
        cache[key] = (value, pins)
        while len(cache) > self._PROGRAM_CACHE_SIZE:
            cache.pop(next(iter(cache)))
        return value

    def _build(self, loss_fn: Callable) -> None:
        """Compile (and cache) the fused step body and the scanned epoch
        over it.  A small FIFO dict keyed by :meth:`_loss_key` holds the
        last few losses' programs, so alternating objectives (task/aux,
        GAN-style) dispatch from cache instead of retracing every call; a
        genuinely new loss rebuilds instead of silently reusing the old
        closure."""
        def build():
            apply = self._apply
            optimizer = self._optimizer
            comm = self.comm
            schedule = self.grad_reduction
            import optax

            def body(params, opt_state, xb, yb):
                def total_loss(p):
                    return loss_fn(apply(p, xb), yb)

                loss, grads = jax.value_and_grad(total_loss)(params)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return loss, optax.apply_updates(params, updates), opt_state

            body_explicit = None
            if schedule in ("bucketed", "fused"):
                # explicit schedule: per-device local gradients inside a
                # shard_map, reduced by hand-placed psums (bucketed
                # reverse-order or one fused collective) — the loss mean
                # over equal shards equals the global batch mean, so the
                # update matches the implicit schedule mathematically
                spec = P(comm.axis_name)
                blocking = schedule == "fused"

                def local_step(params, xl, yl):
                    def local_loss(p):
                        return loss_fn(apply(p, xl), yl)

                    # the replicated parameters enter the body unvarying
                    # (in_specs=P()); differentiating them as such makes
                    # autodiff psum the cotangent over the mesh already.
                    # Cast to varying so the gradient is the per-device
                    # one and reduce_gradients is the ONLY reduction.
                    params = jax.tree_util.tree_map(
                        lambda p: jax.lax.pcast(p, comm.axis_name, to="varying"),
                        params,
                    )
                    loss, grads = jax.value_and_grad(local_loss)(params)
                    grads = reduce_gradients(grads, comm, blocking=blocking)
                    loss = comm.psum(loss) / comm.size
                    return loss, grads

                def explicit_body(params, opt_state, xb, yb):
                    loss, grads = jax.shard_map(
                        local_step,
                        mesh=comm.mesh,
                        in_specs=(P(), spec, spec),
                        out_specs=(P(), P()),
                    )(params, xb, yb)
                    updates, opt_state = optimizer.update(grads, opt_state, params)
                    return loss, optax.apply_updates(params, updates), opt_state

                body_explicit = jax.jit(explicit_body)

            @jax.jit
            def epoch(params, opt_state, xs, ys):
                def scan_body(carry, batch):
                    loss, p, s = body(*carry, *batch)
                    return (p, s), loss

                (params, opt_state), losses = jax.lax.scan(
                    scan_body, (params, opt_state), (xs, ys)
                )
                return params, opt_state, losses

            self._batch_sharding = NamedSharding(
                self.comm.mesh, P(self.comm.axis_name)
            )
            self._stack_sharding = NamedSharding(
                self.comm.mesh, P(None, self.comm.axis_name)
            )
            return jax.jit(body), epoch, body_explicit

        self._train_step, self._epoch_fn, self._train_step_explicit = (
            self._cached_program(self._programs, loss_fn, build)
        )

    def step(self, loss_fn: Callable, x, y) -> float:
        """One fused train step: forward, backward, optimizer update —
        compiled once and cached (the whole of the reference's hook
        machinery plus DataParallelOptimizer.step, dp_optimizer.py:851)."""
        if self._optimizer is None:
            raise RuntimeError("construct DataParallel with an optimizer to use step()")
        self._build(loss_fn)

        xd = x._dense() if isinstance(x, DNDarray) else jnp.asarray(x)
        yd = y._dense() if isinstance(y, DNDarray) else jnp.asarray(y)
        divisible = xd.shape[0] % self.comm.size == 0
        if divisible:
            xd = jax.device_put(xd, self._batch_sharding)
            yd = jax.device_put(yd, self._batch_sharding)
        # explicit schedules run as a shard_map, which needs the batch to
        # tile the mesh; ragged batches fall back to the implicit body
        step_fn = (
            self._train_step_explicit
            if (self._train_step_explicit is not None and divisible)
            else self._train_step
        )
        loss, self.params, self._opt_state = step_fn(self.params, self._opt_state, xd, yd)
        return float(loss)

    def train_steps(self, loss_fn: Callable, xs, ys) -> jnp.ndarray:
        """Run a whole stack of train steps as ONE device program.

        ``xs``/``ys`` carry a leading step axis: ``xs[k]`` is step *k*'s
        batch (each batch sharded over the mesh axis exactly as in
        :meth:`step`).  A ``lax.scan`` threads (params, opt_state) through
        the fused forward/backward/update body, so per-step host dispatch
        — the dominant cost of tiny steps — is paid once per *stack*
        instead of once per step.  This is the
        TPU-native replacement for the reference's per-iteration python
        loop over ``DataParallel`` (data_parallel.py:150) +
        ``DataParallelOptimizer.step`` (dp_optimizer.py:851): steady-state
        training stages a queue of batches in HBM and scans them.

        Returns the per-step losses (a device-resident ``(n_steps,)``
        array; fetch at epoch boundaries, not per step).

        The scanned epoch always uses the implicit gradient schedule —
        inside one compiled scan XLA already owns collective placement
        end to end; explicit bucketed/fused schedules apply to
        :meth:`step`.
        """
        if self._optimizer is None:
            raise RuntimeError("construct DataParallel with an optimizer to use train_steps()")
        if self.params is None:
            raise RuntimeError("call init() or set_params() first")
        self._build(loss_fn)
        xd, yd = self._stage_stack(xs, ys)
        self.params, self._opt_state, losses = self._epoch_fn(
            self.params, self._opt_state, xd, yd
        )
        return losses

    def _stage_stack(self, xs, ys):
        """Place a (n_steps, batch, ...) stack with each batch sharded over
        the mesh axis.  Already-staged arrays pass through untouched, so a
        caller looping epochs over the same stack pays the transfer once."""
        xd = xs._dense() if isinstance(xs, DNDarray) else jnp.asarray(xs)
        yd = ys._dense() if isinstance(ys, DNDarray) else jnp.asarray(ys)
        if xd.shape[0] != yd.shape[0]:
            raise ValueError(
                f"step axes disagree: xs has {xd.shape[0]} batches, ys {yd.shape[0]}"
            )
        if (
            xd.ndim >= 2
            and yd.ndim >= 2
            and xd.shape[1] % self.comm.size == 0
            and yd.shape[1] % self.comm.size == 0
        ):
            if getattr(xd, "sharding", None) != self._stack_sharding:
                xd = jax.device_put(xd, self._stack_sharding)
            if getattr(yd, "sharding", None) != self._stack_sharding:
                yd = jax.device_put(yd, self._stack_sharding)
        return xd, yd


class DataParallelMultiGPU(DataParallel):
    """Hierarchical DP (data_parallel.py:313): torch-DDP-intra-node + DASO
    inter-node in the reference.

    TPU-native topology: the batch is sharded over BOTH axes of a
    :class:`~heat_tpu.parallel.HierarchicalCommunication` mesh — each node
    gets a contiguous batch slab (axis 'global'), further sharded within the
    node (axis 'node').  Parameters are per-node replicas (a stacked pytree,
    leading node dim sharded over 'global', managed by
    :class:`heat_tpu.optim.DASO`): the per-node gradient is a ``vmap`` over
    the node dimension, inside which the mean-loss gradient psums over
    'node' — the reference's intra-node DDP allreduce (:220).  Cross-node
    averaging happens only when DASO decides to sync, as a bf16 all-reduce
    over 'global' (the reference's ``_global_sync``, dp_optimizer.py:450).
    """

    def __init__(
        self,
        module,
        comm: Optional[Communication] = None,
        optimizer: Any = None,
        daso: Optional["Any"] = None,
    ):
        from ..parallel.comm import HierarchicalCommunication
        from ..optim.dp_optimizer import DASO

        if daso is not None:
            # DASO owns the hierarchy; a conflicting explicit comm would
            # shard the batch on one mesh and sync params on another
            if comm is not None and comm != daso.comm:
                raise ValueError(
                    "pass either comm or daso, not both: the DASO instance's "
                    "communication defines the (node x local) grid"
                )
            if not daso.hierarchical:
                raise ValueError(
                    "DataParallelMultiGPU requires a DASO built on a "
                    "HierarchicalCommunication (e.g. DASO(..., comm="
                    "HierarchicalCommunication(grid=(n_node, per_node)))); "
                    "a plain-comm DASO has no node axis to sync across"
                )
            comm = daso.comm
        if not isinstance(comm, HierarchicalCommunication):
            comm = HierarchicalCommunication(devices=comm.devices if comm else None)
        super().__init__(module, comm=comm, optimizer=optimizer)
        if daso is None and optimizer is not None:
            daso = DASO(local_optimizer=optimizer, total_epochs=1, comm=comm,
                        warmup_epochs=0, cooldown_epochs=0)
        self.daso = daso
        self._hier_step = None
        self._hier_programs = {}

    # -- per-node replica parameter state ------------------------------
    def set_params(self, params) -> None:
        if self.daso is None or not self.daso.hierarchical:
            super().set_params(params)
            self._hier_step = None
            self._hier_programs = {}
            return
        self.params = self.daso.replicate(params)
        self._hier_step = None
        self._hier_programs = {}

    def _forward_params(self):
        # inference runs on the node-0 replica (identical everywhere after
        # a sync; representative between syncs)
        if self.daso is not None and self.daso.hierarchical:
            return jax.tree_util.tree_map(lambda p: p[0], self.params)
        return self.params

    def step(self, loss_fn: Callable, x, y) -> float:
        """One hierarchical step: per-node grads (vmap over node replicas,
        psum over 'node' inside) + DASO's skipped/delayed global sync."""
        if self.daso is None or not self.daso.hierarchical:
            return super().step(loss_fn, x, y)
        comm = self.comm
        n_node = comm.num_nodes
        # own cache slots: the base _build programs have a different
        # signature, and mixing step()/train_steps() must not collide
        def build():
            apply = self._apply

            @jax.jit
            def grad_step(stacked, xn, yn):
                def node_loss(p, xi, yi):
                    return loss_fn(apply(p, xi), yi)

                losses, grads = jax.vmap(jax.value_and_grad(node_loss))(stacked, xn, yn)
                return losses.mean(), grads

            self._hier_sharding = NamedSharding(
                comm.mesh, P(comm.global_axis, comm.node_axis)
            )
            return grad_step

        self._hier_step = self._cached_program(self._hier_programs, loss_fn, build)

        xd = x._dense() if isinstance(x, DNDarray) else jnp.asarray(x)
        yd = y._dense() if isinstance(y, DNDarray) else jnp.asarray(y)
        b = xd.shape[0]
        if b % n_node != 0:
            raise ValueError(f"batch {b} not divisible by {n_node} nodes")
        xn = xd.reshape((n_node, b // n_node) + xd.shape[1:])
        yn = yd.reshape((n_node, b // n_node) + yd.shape[1:])
        if (b // n_node) % comm.node_size == 0:
            xn = jax.device_put(xn, self._hier_sharding)
            yn = jax.device_put(yn, self._hier_sharding)
        loss, grads = self._hier_step(self.params, xn, yn)
        self.params = self.daso.step(self.params, grads)
        return float(loss)

    def train_steps(self, loss_fn: Callable, xs, ys) -> jnp.ndarray:
        """Always raises: DASO's skipped/delayed global sync is host-side
        control flow between steps and cannot ride inside one scanned
        program (and every constructible instance with an optimizer owns a
        hierarchical DASO)."""
        raise NotImplementedError(
            "train_steps does not drive the DASO hierarchical sync "
            "protocol; call step() per batch (DASO decides syncs between "
            "steps), or use a plain DataParallel for scanned epochs"
        )

    def collect_params(self):
        """One coherent (node-0) parameter pytree (after :meth:`DASO.last_batch`
        the replicas are identical up to bf16 transport)."""
        if self.daso is not None and self.daso.hierarchical:
            return self.daso.collect(self.params)
        return self.params

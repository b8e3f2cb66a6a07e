"""The jit-compiled compute plane: train / evaluate / predict steps.

This replaces the reference worker's TF2-eager gradient path
(worker/worker.py:730-870: forward, tape.gradient, report_gradient to PS) and
the entire PS apply path (ps/servicer.py push_gradients →
OptimizerWrapper.apply_gradients; Go server.go → optimizer.go → Eigen
kernels). On TPU all of that is ONE compiled XLA program per step:

    forward + backward + optax update, sharded over the mesh —
    gradient reduction is not an RPC but the psum XLA inserts because the
    batch is sharded over (dp, fsdp) while params are replicated/sharded.

Design notes (TPU-first):
* static shapes everywhere — partial batches are padded host-side
  (data/dataset.pad_batch) and masked via each example's weight column;
* state is donated (`donate_argnums`) so params/opt-state update in place
  in HBM;
* models come from the zoo convention (flax.linen Module whose __call__
  takes a feature dict and `training` flag);
* loss signature parity with the reference zoo: loss(labels, predictions),
  with an optional 3rd `sample_weights` arg picked up by introspection.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from flax.core import FrozenDict

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.parallel.sharding import (
    infer_state_pspec,
    pspec_to_sharding,
)


@struct.dataclass
class TrainState:
    step: jax.Array
    params: any = struct.field(pytree_node=True)
    opt_state: any = struct.field(pytree_node=True)
    model_state: any = struct.field(pytree_node=True)  # batch_stats etc.
    rng: jax.Array = struct.field(pytree_node=True)
    # Per-table row-optimizer slots for sparse-grad embedding tables
    # ({table_path_str: optax state}); empty for dense-only models.
    # See embedding/sparse_update.py.
    embed_opt_state: any = struct.field(pytree_node=True, default_factory=dict)

    @property
    def version(self):
        """Model version = step count (the reference's PS `version` that
        workers/eval sync on is the number of applied updates)."""
        return int(self.step)


def _split_label(batch):
    """Zoo datasets yield (features_dict, labels) for train/eval and bare
    features for prediction (reference dataset_fn convention)."""
    if isinstance(batch, tuple) and len(batch) == 2:
        return batch[0], batch[1]
    return batch, None


class Trainer(object):
    """Owns the model/optimizer from a ModelSpec and the compiled steps.

    One Trainer per process; the same object backs the LocalExecutor
    (reference elasticdl/local_executor.py) and the distributed Worker
    (reference worker/worker.py).
    """

    def __init__(self, model_spec, mesh=None, model_params="", seed=0,
                 compute_dtype=None, callbacks=None,
                 embedding_partition_threshold=None, grad_accum_steps=1,
                 trainable_pattern=None):
        self.spec = model_spec
        self.model = model_spec.create_model(model_params)
        from elasticdl_tpu.embedding.sparse_optim import make_row_sparse

        tx = model_spec.optimizer()
        if callbacks is None and model_spec.callbacks_fn is not None:
            callbacks = model_spec.callbacks_fn()
        tx, self._lr_multiplier_fn = _apply_lr_scheduler(tx, callbacks)
        # The raw transform: reused per-table by the row-sparse engine
        # (embedding/sparse_update.py — optax state leaves are
        # elementwise, so applying the same tx to gathered rows is the
        # reference OptimizerWrapper's "stock optimizer on looked-up
        # rows+slots", ps/optimizer_wrapper.py:70-351).
        self._base_tx = tx
        # Row-sparse embedding semantics for small (non-tapped) tables
        # (dense update + mask: untouched rows and slots don't move).
        # Identity for models without embedding tables.
        self.tx = make_row_sparse(tx)
        # Gradient accumulation (the reference worker's local-update mode,
        # worker.py:822-828/1007-1089: accumulate per-minibatch gradients
        # and push to the PS every `get_model_steps`). Here the PS round
        # trip is gone, so the TPU-native semantics are optax.MultiSteps:
        # each train_step call is one microbatch; the dense optimizer
        # applies the averaged gradient every Nth call and emits zero
        # updates in between. Sparse-tapped embedding tables and host-
        # spill tables keep their per-microbatch row updates (the
        # reference likewise pushed embedding grads through the
        # OptimizerWrapper on every report).
        self.grad_accum_steps = max(1, int(grad_accum_steps))
        # Fine-tuning: regex over '/'-joined param paths (e.g.
        # "head|block_7" trains the LM head and the last block).
        # Non-matching params are FROZEN via optax.set_to_zero inside
        # the transform — not by zeroing gradients, which would still
        # let decoupled weight decay (adamw) move frozen weights.
        # Applies to the dense optimizer path; sparse-row/host-spill
        # embedding engines keep their own update schedule.
        self.trainable_pattern = trainable_pattern
        # Filled by init_state once the model structure is known:
        self._sparse_paths = {}
        self._train_tx = None
        self._perturb_shapes = {}
        self.embedding_partition_threshold = embedding_partition_threshold
        self.mesh = mesh if mesh is not None else mesh_lib.local_mesh()
        self.seed = seed
        self.compute_dtype = compute_dtype
        self._loss_takes_weights = (
            len(inspect.signature(model_spec.loss).parameters) >= 3
        )
        if not self._loss_takes_weights:
            logger.warning(
                "loss() takes no sample_weights arg: padded rows of partial "
                "final batches will enter the loss unmasked (add a 3rd "
                "`sample_weights` parameter for exact partial-batch math)"
            )
        self._train_step = None
        self._eval_step = None
        self._predict_step = None
        self._state_sharding = None
        self._defer_sparse = False
        self._sparse_stage = []
        self._apply_rows_fn = None
        # Host-spill embedding bridge (embedding/host_bridge.py): pulls
        # rows before the compiled step, applies row grads after it.
        self._host_manager = None
        # Tier-health counters: host-tier apply/stage failures degrade
        # to "those rows miss one update" by design (see _host_apply);
        # these make the degradation observable instead of grep-able.
        # Cumulative for the Trainer's lifetime; the worker forwards
        # them to the master as tier/ exec counters, which the master
        # turns into TensorBoard gauges.
        self.tier_health = {
            "host_failed_cycles": 0,
            "host_dropped_row_updates": 0,
        }

    # ------------------------------------------------------- host bridge

    def attach_host_embeddings(self, manager):
        """Register a HostEmbeddingManager. Must happen before the first
        init_state/train_step so the compiled signature includes the
        pulled-row inputs. Multi-host SPMD: enable_spmd the manager and
        drive training through the assembled path (worker._spmd_step) —
        the local train_step/forward entry points reject SPMD-mode
        managers."""
        if self._train_step is not None or self._eval_step is not None:
            raise RuntimeError(
                "attach_host_embeddings must precede step compilation"
            )
        self._host_manager = manager
        return self

    @property
    def host_manager(self):
        return self._host_manager

    def _host_prepare(self, features):
        if self._host_manager:
            return self._host_manager.prepare(features)
        return features

    # ---------------------------------------------------------------- init

    def init_state(self, example_batch):
        """Initialize params/opt-state sharded over the mesh.

        The reference initializes variables lazily on the worker's first
        minibatch and pushes them to the PS (worker.py:664-701
        `_run_model_call_before_training`); here the same "first batch
        defines the variables" contract seeds a sharded jit init.
        """
        from elasticdl_tpu.embedding import sparse_update

        features, _ = _split_label(example_batch)
        features = self._host_prepare(features)
        features = jax.tree.map(jnp.asarray, features)
        root_rng = jax.random.PRNGKey(self.seed)
        init_rng, state_rng = jax.random.split(root_rng)

        # Structure pass: discover sparse-grad embedding taps (flax
        # perturbations the layer creates at init) and derive the dense
        # transform that excludes those tables.
        var_shapes = jax.eval_shape(
            lambda r, f: self.model.init(
                {"params": r, "dropout": r}, f, training=False
            ),
            init_rng, features,
        )
        perturb_shapes = dict(var_shapes).get(
            sparse_update.PERTURB_COLLECTION, {}
        )
        # nn.with_partitioning annotations (TP model families): collected
        # from the boxed init shapes, honored by infer_state_pspec, and
        # stripped from the stored params below (unbox).
        from elasticdl_tpu.parallel.sharding import collect_annotations

        self._param_annotations = collect_annotations(
            dict(var_shapes).get("params", {})
        )
        self._perturb_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
            perturb_shapes,
        )
        self._sparse_paths = sparse_update.sparse_table_paths(
            perturb_shapes
        )
        self._train_tx = sparse_update.split_dense_tx(
            self.tx, set(self._sparse_paths)
        )
        if self.trainable_pattern:
            # the freeze wraps the DENSE transform only; sparse-row and
            # host-spill embedding tiers run their own update engines
            # and would silently keep training — refuse instead of
            # breaking the "non-matching params do not move" contract
            import re as _re

            _rex = _re.compile(self.trainable_pattern)
            escaped = [
                p for p in self._sparse_paths
                if not _rex.search("/".join(str(k) for k in p))
            ]
            if escaped or self._host_manager is not None:
                raise NotImplementedError(
                    "trainable_pattern freezes the dense optimizer "
                    "path only; %s run their own update engines. "
                    "Match them in the pattern, or disable the tier "
                    "(sparse_grads=False / no host_embeddings) for "
                    "fine-tuning."
                    % (
                        "host-spill tables" if self._host_manager
                        else "sparse-row tables %s" % (escaped,)
                    )
                )
            self._train_tx = _freeze_except(
                self._train_tx, self.trainable_pattern
            )
        if self.grad_accum_steps > 1:
            # Every tier shares ONE schedule (k microbatches -> one
            # applied update): the dense tier through optax.MultiSteps
            # (mean of k grads), the sparse-row tier by staging each
            # microbatch's (ids, row grads)/k host-side and applying the
            # concatenation at the macro boundary (apply_flat_row_updates
            # — dedup sums across microbatches), and the host-spill tier
            # via HostEmbeddingManager.stage/apply_staged. Engines and
            # row_tx step counters therefore advance once per macro step,
            # exactly like a k-times-larger batch.
            import optax

            self._train_tx = optax.MultiSteps(
                self._train_tx, every_k_schedule=self.grad_accum_steps
            )
        self._defer_sparse = bool(
            self._sparse_paths and self.grad_accum_steps > 1
        )
        self._sparse_stage = []
        self._apply_rows_fn = None

        def init_fn(rng, feats):
            from flax.linen import meta as nn_meta

            variables = self.model.init(
                {"params": rng, "dropout": rng}, feats, training=False
            )
            variables = dict(nn_meta.unbox(variables))
            params = variables.pop("params")
            variables.pop(sparse_update.PERTURB_COLLECTION, None)
            variables.pop(sparse_update.SPARSE_IDS_COLLECTION, None)
            opt_state = self._train_tx.init(params)
            embed_opt = sparse_update.init_row_opt_states(
                self._base_tx, params, self._sparse_paths
            )
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=opt_state,
                model_state=FrozenDict(variables),
                rng=state_rng,
                embed_opt_state=embed_opt,
            )

        state_shapes = jax.eval_shape(init_fn, init_rng, features)
        kwargs = {"annotations": self._param_annotations}
        if self.embedding_partition_threshold is not None:
            kwargs["embedding_threshold_bytes"] = (
                self.embedding_partition_threshold
            )
        pspecs = infer_state_pspec(state_shapes, self.mesh, **kwargs)
        self._state_sharding = pspec_to_sharding(pspecs, self.mesh)
        with self.mesh:
            state = jax.jit(
                init_fn, out_shardings=self._state_sharding
            )(init_rng, features)
        n_params = sum(
            int(np.prod(x.shape))
            for x in jax.tree.leaves(state.params)
        )
        logger.info(
            "Initialized model: %d parameters, mesh axes %s",
            n_params, dict(self.mesh.shape),
        )
        return state

    # --------------------------------------------------------------- steps

    def _compute_loss(self, labels, predictions, weights):
        if self._loss_takes_weights:
            return self.spec.loss(labels, predictions, weights)
        return self.spec.loss(labels, predictions)

    def _build_train_step(self):
        from elasticdl_tpu.embedding import sparse_update

        batch_sh = mesh_lib.batch_sharding(self.mesh)
        repl = mesh_lib.replicated(self.mesh)
        tx = self._train_tx if self._train_tx is not None else self.tx
        sparse_paths = self._sparse_paths
        perturb_shapes = self._perturb_shapes
        ids_coll = sparse_update.SPARSE_IDS_COLLECTION
        # Pulled host-table rows are differentiable inputs: their grads
        # (the backward scatter-add of rows[idx]) are the per-unique-row
        # gradients the host engines apply (embedding/host_bridge.py).
        host_keys = (
            self._host_manager.rows_keys() if self._host_manager else ()
        )

        def train_step(state, features, labels, weights):
            dropout_rng = jax.random.fold_in(state.rng, state.step)
            # The row-grad taps are identically-zero perturbations
            # rebuilt every step (XLA folds the zeros); their gradients
            # are the per-row embedding grads.
            perturbs = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), perturb_shapes
            )
            host_rows = {k: features[k] for k in host_keys}
            base_features = {
                k: v for k, v in features.items() if k not in host_keys
            }

            def loss_fn(params, perturbs, host_rows):
                features = dict(base_features, **host_rows)
                variables = {"params": params, **state.model_state}
                if sparse_paths:
                    variables[sparse_update.PERTURB_COLLECTION] = perturbs
                mutable = [k for k in state.model_state if k != "params"]
                if sparse_paths:
                    mutable = mutable + [ids_coll]
                # `mutable` is collection NAMES from the state pytree —
                # static structure, not traced values
                if mutable:  # edl-lint: disable=EDL102
                    preds, new_mut = self.model.apply(
                        variables,
                        features,
                        training=True,
                        mutable=mutable,
                        rngs={"dropout": dropout_rng},
                    )
                    new_mut = dict(new_mut)
                    ids = new_mut.pop(ids_coll, {})
                    new_model_state = new_mut
                else:
                    preds = self.model.apply(
                        variables,
                        features,
                        training=True,
                        rngs={"dropout": dropout_rng},
                    )
                    new_model_state = state.model_state
                    ids = {}
                return (
                    self._compute_loss(labels, preds, weights),
                    (new_model_state, ids),
                )

            (loss_val, (new_model_state, ids)), grads = jax.value_and_grad(
                loss_fn, argnums=(0, 1, 2), has_aux=True
            )(state.params, perturbs, host_rows)
            param_grads, perturb_grads, host_grads = grads
            updates, new_opt_state = tx.update(
                param_grads, state.opt_state, state.params
            )
            new_params = jax.tree.map(
                lambda p, u: (p + u).astype(p.dtype),
                state.params,
                updates,
            )
            embed_opt = state.embed_opt_state
            sparse_aux = {}
            if sparse_paths and not self._defer_sparse:
                new_params, embed_opt = sparse_update.apply_row_updates(
                    self._base_tx, new_params, embed_opt,
                    perturb_grads, ids, sparse_paths,
                )
            elif sparse_paths:
                # gradient accumulation: defer the row update — emit this
                # microbatch's (ids, row grads) per table for host-side
                # staging; the macro boundary applies the concatenation
                # (apply_flat_row_updates)
                pg_flat = {}
                from flax import traverse_util

                flat = traverse_util.flatten_dict(dict(perturb_grads))
                for table_path, perturb_path in sparse_paths.items():
                    key = sparse_update.path_str(table_path)
                    ids_flat = jnp.asarray(
                        sparse_update.extract_ids(ids, perturb_path),
                        jnp.int32,
                    ).reshape(-1)
                    grads = flat[perturb_path]
                    pg_flat[key] = (
                        ids_flat,
                        grads.reshape(ids_flat.shape[0], -1),
                    )
                sparse_aux = pg_flat
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
                model_state=FrozenDict(new_model_state),
                embed_opt_state=embed_opt,
            )
            return new_state, loss_val, host_grads, sparse_aux

        return jax.jit(
            train_step,
            donate_argnums=(0,),
            in_shardings=(self._state_sharding, batch_sh, batch_sh, batch_sh),
            out_shardings=(self._state_sharding, repl, repl, repl),
        )

    def _build_eval_step(self):
        batch_sh = mesh_lib.batch_sharding(self.mesh)
        repl = mesh_lib.replicated(self.mesh)

        def eval_step(state, features):
            variables = {"params": state.params, **state.model_state}
            preds = self.model.apply(variables, features, training=False)
            return preds

        return jax.jit(
            eval_step,
            in_shardings=(self._state_sharding, batch_sh),
            out_shardings=repl,
        )

    # ---------------------------------------------------------------- API

    def train_step(self, state, batch, true_count=None):
        """One optimizer update. `batch` = (features, labels) numpy dicts
        already padded to the static batch size; `true_count` masks padding.
        Returns (new_state, float loss)."""
        features, labels = _split_label(batch)
        bsz = _leading_dim(features)
        weights = _make_weights(bsz, true_count)
        self._reject_spmd_host_local_path("train_step")
        with tracing.phase("trainer.host_prepare"):
            features = self._host_prepare(features)
            # int(state.step) forces a host sync (blocks on the previous
            # step's output); only pay it when a host/sparse tier
            # actually consumes it, so dense models keep async dispatch
            # overlap
            tiers = self._host_manager is not None or self._defer_sparse
            pre_step = int(state.step) if tiers else 0
            scale = self._host_lr_scale(pre_step) if tiers else 1.0
        with tracing.phase("trainer.dispatch"):
            state, loss, host_grads, sparse_aux = self._run_train_step(
                state, features, labels, weights
            )
        if tiers:
            with tracing.phase("trainer.post_tiers"):
                state = self._post_step_tiers(
                    pre_step, state, host_grads, sparse_aux, scale
                )
        return state, loss

    def _host_lr_scale(self, pre_step):
        """scale_by_schedule counts applied updates from 0, i.e. the
        pre-update step number — mirror it for the host tier (under
        gradient accumulation: the macro-step index). The multiplier
        runs BEFORE the donating compiled step: a user schedule that
        raises must fail while the caller's state buffers are still
        alive and the batch retryable."""
        if self._host_manager and self._lr_multiplier_fn is not None:
            return float(
                self._lr_multiplier_fn(pre_step // self.grad_accum_steps)
            )
        return 1.0

    def _post_step_tiers(self, pre_step, state, host_grads, sparse_aux,
                         scale):
        """Apply (or stage) the host-spill and sparse-row tiers after
        the compiled step. With grad_accum_steps == 1 this is the
        immediate apply; otherwise each microbatch stages its row grads
        weighted 1/k and the macro boundary (every k-th microbatch)
        applies the merged cycle, keeping every tier on the MultiSteps
        schedule."""
        accum = self.grad_accum_steps
        boundary = accum == 1 or pre_step % accum == accum - 1
        if self._host_manager:
            if accum == 1:
                self._host_apply(host_grads, scale)
            else:
                # Separate accounting per op: a failed stage() loses
                # only the CURRENT microbatch (the buffer is untouched
                # and prior microbatches still apply at the boundary),
                # while a failed apply_staged() loses everything it
                # drained — snapshot staged_row_count BEFORE the drain.
                try:
                    self._host_manager.stage(host_grads,
                                             weight=1.0 / accum)
                except Exception:
                    self._count_dropped_host_rows(
                        self._host_rows_at_risk(staged=False)
                    )
                    logger.exception(
                        "host-embedding stage failed; this "
                        "microbatch's rows miss the cycle (no retry: "
                        "state donated)"
                    )
                if boundary:
                    at_risk = self._host_rows_at_risk(pending=False)
                    try:
                        self._host_manager.apply_staged(lr_scale=scale)
                    except Exception:
                        self._count_dropped_host_rows(at_risk)
                        logger.exception(
                            "host-embedding apply_staged failed; the "
                            "staged cycle's rows miss this update (no "
                            "retry: state donated)"
                        )
        if self._defer_sparse:
            self._sparse_stage.append(
                jax.tree.map(np.asarray, sparse_aux)
            )
            if boundary:
                state = self._apply_sparse_staged(state)
        return state

    def _apply_sparse_staged(self, state):
        """Macro-boundary sparse-row apply: concatenate the staged
        microbatches per table (grads pre-scaled by 1/k at stage time)
        and run ONE row_sparse update — identical math to a k-times
        batch (dedup sums repeats across microbatches; row_tx scalar
        step advances once)."""
        from elasticdl_tpu.embedding import sparse_update

        staged, self._sparse_stage = self._sparse_stage, []
        merged = {}
        for key in staged[0]:
            ids = np.concatenate([m[key][0] for m in staged])
            grads = np.concatenate(
                [m[key][1] / self.grad_accum_steps for m in staged]
            )
            merged[key] = (ids, grads)
        if self._apply_rows_fn is None:
            repl = mesh_lib.replicated(self.mesh)

            def apply_rows(state, merged):
                new_params, new_embed = (
                    sparse_update.apply_flat_row_updates(
                        self._base_tx, state.params,
                        state.embed_opt_state, merged,
                        self._sparse_paths,
                    )
                )
                return state.replace(
                    params=new_params, embed_opt_state=new_embed
                )

            self._apply_rows_fn = jax.jit(
                apply_rows,
                donate_argnums=(0,),
                in_shardings=(self._state_sharding, repl),
                out_shardings=self._state_sharding,
            )
        with self.mesh:
            return self._apply_rows_fn(state, merged)

    def _host_apply(self, host_grads, scale):
        """Apply host-tier row grads after the compiled step. A failure
        here must NOT propagate: the compiled step donated the caller's
        old state buffers, so a retry would replay on deleted arrays
        (bricking the worker's 64-retry loop) and double-apply any
        engine that did step. Instead the affected rows miss this one
        update — the degradation the reference's PS path also accepted
        (dropped grads on PS restart; fault tolerance is
        task-requeue-first, README.md:62-66)."""
        if not self._host_manager:
            return
        at_risk = self._host_rows_at_risk(staged=False)
        try:
            self._host_manager.apply(host_grads, lr_scale=scale)
        except Exception:
            self._count_dropped_host_rows(at_risk)
            # The log itself must not touch device values: with an
            # async device error poisoning this step's outputs,
            # int(state.step) would re-raise the very exception this
            # handler exists to contain.
            logger.exception(
                "host-embedding apply failed; affected rows miss "
                "this update (no retry: state is donated)"
            )

    def _host_rows_at_risk(self, pending=True, staged=True):
        """Row updates a tier failure would drop: the current
        microbatch's pulled rows (`pending`) and/or the accumulation
        buffer (`staged`) — callers pick the component the failing op
        actually loses. Never raises (feeds exception handlers)."""
        try:
            rows = 0
            if pending:
                rows += self._host_manager.pending_row_count()
            if staged:
                rows += self._host_manager.staged_row_count()
            return rows
        except Exception:
            return 0

    def _count_dropped_host_rows(self, rows):
        """Record one failed host-tier cycle in tier_health. Runs inside
        the apply/stage exception handlers, so it must never raise."""
        self.tier_health["host_failed_cycles"] += 1
        self.tier_health["host_dropped_row_updates"] += int(rows)

    def train_step_assembled(self, state, features, labels, weights):
        """Run the compiled step on already-prepared (possibly global
        multi-host) arrays — the SPMD path (parallel/spmd.py). Host-spill
        features must already be prepared (the worker calls
        host_manager.prepare BEFORE assembling, since the multi-host
        prepare is itself a host-level collective); the row grads are
        applied here, each host updating its owned id partition."""
        tiers = self._host_manager is not None or self._defer_sparse
        pre_step = int(state.step) if tiers else 0
        scale = self._host_lr_scale(pre_step) if tiers else 1.0
        state, loss, host_grads, sparse_aux = self._run_train_step(
            state, features, labels, weights
        )
        if tiers:
            state = self._post_step_tiers(
                pre_step, state, host_grads, sparse_aux, scale
            )
        return state, loss

    def _run_train_step(self, state, features, labels, weights):
        if self._train_step is None:
            self._train_step = self._build_train_step()
        with self.mesh:
            return self._train_step(state, features, labels, weights)

    def forward(self, state, features):
        """Inference forward pass (evaluation / prediction). Output is
        replicated to every host."""
        self._reject_spmd_host_local_path("forward")
        features = self._host_prepare(features)
        return self.forward_assembled(state, features)

    def _reject_spmd_host_local_path(self, entry):
        """With the host manager in SPMD mode, prepare() emits idx over
        GLOBAL row positions — feeding that to the local (un-assembled)
        step would make jnp.take clamp out-of-range rows silently. Fail
        fast instead: the worker's assembled path is the only correct
        entry."""
        if (self._host_manager is not None
                and self._host_manager.spmd_ctx is not None):
            raise ValueError(
                "%s() is the local single-host path, but the host-"
                "embedding manager is in SPMD mode; prepare locally and "
                "use train_step_assembled / forward_assembled (see "
                "worker._spmd_step)" % entry
            )

    def forward_assembled(self, state, features):
        """Forward on already-prepared (possibly global multi-host)
        arrays — the SPMD eval path; host-spill features must already be
        prepared (worker._spmd_eval_step prepares before assembling)."""
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        with self.mesh:
            return self._eval_step(state, features)

    def make_weights(self, batch_size, true_count):
        return _make_weights(batch_size, true_count)

    def evaluate_batch(self, state, batch, true_count=None):
        """Returns (outputs, labels) trimmed to true_count, for master-side
        metric aggregation (reference worker.py report_evaluation_metrics).
        Outputs may be a dict for multi-output models."""
        features, labels = _split_label(batch)
        preds = self.forward(state, features)

        def trim(x):
            x = np.asarray(x)
            return x[:true_count] if true_count is not None else x

        if isinstance(preds, dict):
            preds = {k: trim(v) for k, v in preds.items()}
        else:
            preds = trim(preds)
        labels = trim(labels) if labels is not None else None
        return preds, labels


def _freeze_except(tx, pattern):
    """Wrap `tx` so only params whose '/'-joined path matches the regex
    train; everything else gets optax.set_to_zero() (true freezing —
    no optimizer-side movement, including adamw's decoupled weight
    decay). Labels are derived from the params pytree at init time, so
    any model structure works."""
    import re

    import optax

    rex = re.compile(pattern)

    def labels(params):
        def one(path, _):
            name = "/".join(
                str(getattr(k, "key", k)) for k in path
            )
            return "train" if rex.search(name) else "freeze"

        out = jax.tree_util.tree_map_with_path(one, params)
        flat = jax.tree_util.tree_leaves(out)
        n_train = sum(1 for v in flat if v == "train")
        logger.info(
            "trainable_pattern %r: %d/%d param tensors train",
            pattern, n_train, len(flat),
        )
        if n_train == 0:
            logger.warning(
                "trainable_pattern %r matches NOTHING — every "
                "parameter is frozen and training is a no-op", pattern,
            )
        return out

    return optax.multi_transform(
        {"train": tx, "freeze": optax.set_to_zero()}, labels
    )


def _apply_lr_scheduler(tx, callbacks):
    """Chain an optax scale_by_schedule when a LearningRateScheduler
    callback is present (api/callbacks.py: version → LR multiplier,
    compiled into the step). Returns (tx, multiplier_fn or None) — the
    multiplier also scales host-engine row updates so every parameter
    tier sees the same schedule."""
    import optax

    from elasticdl_tpu.api.callbacks import LearningRateScheduler

    for cb in callbacks or []:
        if isinstance(cb, LearningRateScheduler):
            return optax.chain(
                tx, optax.scale_by_schedule(cb.multiplier_fn)
            ), cb.multiplier_fn
    return tx, None


def _leading_dim(features):
    if isinstance(features, dict):
        return next(iter(features.values())).shape[0]
    return features.shape[0]


def _make_weights(batch_size, true_count):
    if true_count is None or true_count >= batch_size:
        return np.ones((batch_size,), np.float32)
    w = np.zeros((batch_size,), np.float32)
    w[:true_count] = 1.0
    return w

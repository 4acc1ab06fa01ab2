"""Ullmann-refined Particle Swarm Optimization for subgraph matching.

Faithful implementation of paper Algorithm 1. Each particle carries a
continuously-relaxed mapping S ∈ [0,1]^{n×m} (row-stochastic, masked by the
global compatibility Mask). Per epoch:

  1. InitParticles          — fresh swarm (global bests persist across epochs)
  2. K inner steps          — ONE fused launch through the backend seam
                              (KernelBackend.epoch_fused): velocity/position/
                              mask/normalize update, optional requantize,
                              fitness -‖Q-SGSᵀ‖², local & global best
                              tracking — particle state stays kernel-resident
                              for the whole epoch on the Pallas path
  3. Projection             — greedy argmax assignment M̃ (comparator tree)
  4. UllmannRefine          — candidate set from S ∪ M̃, matrix-form pruning
                              sweeps, re-projection → M̂
  5. IsFeasible             — M̂ G M̂ᵀ ⊇ Q and injectivity
  6. EliteConsensus         — S̄ = softmax-weighted elite average (the global
                              controller's consensus-guided direction)

Everything is vmapped over particles and jit-compiled; the epoch loop is a
``lax.scan`` so the whole matcher is a single XLA program (this is what the
dry-run lowers onto the production mesh).

Quantized mode (paper §3.4): S is re-quantized to uint8 after every update
(straight-through), fitness runs on the int8/int32 MAC path, and row
renormalization uses the divide-free reciprocal-multiply model.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend as kernel_backend


@dataclasses.dataclass(frozen=True)
class PSOConfig:
    """Static configuration of Algorithm 1 (one frozen value per knob).

    Every field is trace-static: two configs that differ in ANY field
    compile (and AOT-cache, and snapshot-validate) as different
    programs — ``kernels.backend.config_digest`` hashes all of them, so
    the service's persisted executables and warm-state snapshots are
    automatically invalidated by a config drift. Fields are documented
    inline below; swarm-shape fields (``num_particles``/``epochs``/
    ``inner_steps``) set array shapes, the float knobs are baked-in
    constants, and the ``backend``/``quantized``/``prune_mask``/
    ``early_exit`` family selects which kernels the traced program
    calls.
    """
    num_particles: int = 64          # N (per device in the sharded matcher)
    epochs: int = 4                  # T
    inner_steps: int = 12            # K
    omega: float = 0.7               # inertia
    c1: float = 1.4                  # cognitive (S_local)
    c2: float = 1.4                  # social (S*)
    c3: float = 0.6                  # consensus (S̄) — the paper's addition
    v_max: float = 0.5               # velocity clamp per S entry
    elite_frac: float = 0.25         # top-k fraction fused into S̄
    consensus_temp: float = 25.0     # softmax temperature on normalized f
    refine_threshold: float = 0.5    # S ≥ τ·rowmax(S) enters the candidate set
    refine_iters: int = 6            # Ullmann pruning sweeps
    quantized: bool = False          # uint8 S + int32-MAC fitness (§3.4)
    backend: str = "auto"            # KernelBackend registry name
                                     # ("ref" | "pallas" | "interpret");
                                     # "auto" defers to the
                                     # REPRO_KERNEL_BACKEND env var, then
                                     # the platform default
    prune_mask: bool = True          # global Ullmann+injectivity pre-prune
    prune_iters: int = 0             # 0 = iterate the pre-prune to fixpoint
    early_exit: bool = False         # stop epochs once a good mapping exists
    early_exit_fitness: float = float("-inf")   # "good" = feasible ∧ f ≥ this
    carry_fastpath: bool = True      # with early_exit: verify the warm
                                     # carry's S* by one projection and skip
                                     # every epoch if it is still feasible
    gumbel_tau: float = 0.0          # >0: per-particle Gumbel-perturbed
                                     # structured projection (diversity after
                                     # consensus collapse; off by default)

    def replace(self, **kw) -> "PSOConfig":
        return dataclasses.replace(self, **kw)


class SwarmState(dict):
    """Light pytree: S, V, S_local, f_local, S_star, f_star, S_bar."""


def init_particles(key: jax.Array, num: int, mask: jax.Array):
    """Random masked row-stochastic mappings + zero velocities."""
    n, m = mask.shape
    u = jax.random.uniform(key, (num, n, m), minval=0.05, maxval=1.0)
    s = u * mask.astype(jnp.float32)[None]
    row = s.sum(-1, keepdims=True)
    mask_rows = mask.astype(jnp.float32).sum(-1, keepdims=True)[None]
    uniform = mask.astype(jnp.float32)[None] / jnp.maximum(mask_rows, 1.0)
    s = jnp.where(row > 1e-9, s / jnp.maximum(row, 1e-9), uniform)
    v = jnp.zeros_like(s)
    return s, v


def _fitness(S, Q, G, cfg: PSOConfig):
    bk = kernel_backend.for_config(cfg)
    if cfg.quantized:
        Sq = bk.quantize_s(S)
        f = bk.edge_fitness_quantized(Sq, Q, G)
        return f / (255.0 ** 4)   # rescale to float-fitness units
    return bk.edge_fitness(S, Q, G)


def _maybe_requantize(S, mask, cfg: PSOConfig):
    """Straight-through uint8 re-quantization of the swarm state (models the
    accelerator keeping S resident in uint8 between steps)."""
    if not cfg.quantized:
        return S
    bk = kernel_backend.for_config(cfg)
    Sq = jax.vmap(bk.row_normalize_quantized, in_axes=(0, None))(
        bk.quantize_s(S), mask)
    return bk.dequantize_s(Sq)


def elite_k_for(cfg: PSOConfig) -> int:
    """Static elite count k = max(1, round(elite_frac · N)) (line 24)."""
    return max(1, int(round(cfg.elite_frac * cfg.num_particles)))


def elite_consensus(S_all, f_all, cfg: PSOConfig):
    """S̄: softmax-weighted average of the elite fraction (paper line 24).

    Also returns (weighted_sum, weight_total) so the distributed matcher can
    psum the parts across devices before dividing. Thin wrapper over the
    backend seam (``KernelBackend.elite_consensus``) — the fused epoch
    tail computes the same reduction beside its kernel launch.
    """
    bk = kernel_backend.for_config(cfg)
    k = max(1, int(round(cfg.elite_frac * S_all.shape[0])))
    return bk.elite_consensus(S_all, f_all, elite_k=k,
                              consensus_temp=cfg.consensus_temp)


def ullmann_refine_candidates(S, M_proj, Q, G, mask, cfg: PSOConfig):
    """Paper line 20: refine the particle's candidate structure with Ullmann
    pruning sweeps, then re-project. Batched over particles. Thin wrapper
    over the backend seam (``KernelBackend.ullmann_refine_candidates``) —
    the fused epoch tail runs the same refinement in-kernel."""
    bk = kernel_backend.for_config(cfg)
    return bk.ullmann_refine_candidates(
        S, M_proj, Q, G, mask, refine_threshold=cfg.refine_threshold,
        refine_iters=cfg.refine_iters)


def _epoch_start(carry, key, Q, G, mask, cfg: PSOConfig):
    """Epoch prologue (one problem): key splits, fresh swarm, initial
    fitness, global-best seeding, and the pre-drawn per-step randoms.

    The key-split topology is exactly the pre-fusion ``run_epoch``'s
    (3-way with gumbel, else 2-way), and ``r_all[k]`` equals the
    ``uniform(split(k_steps, K)[k], (N, 3))`` draw the legacy inner
    scan made at step k — hoisting the draws out of the loop is what
    lets the fused kernel consume the identical random stream.
    """
    S_star, f_star, _ = carry
    if cfg.gumbel_tau > 0:
        k_init, k_steps, k_gum = jax.random.split(key, 3)
    else:
        k_init, k_steps = jax.random.split(key)
        k_gum = key   # unused: cfg.gumbel_tau == 0 never draws from it
    S, V = init_particles(k_init, cfg.num_particles, mask)
    f_local = _fitness(S, Q, G, cfg)

    # seed global best from the fresh swarm if better
    best0 = jnp.argmax(f_local)
    better0 = f_local[best0] > f_star
    S_star = jnp.where(better0, S[best0], S_star)
    f_star = jnp.where(better0, f_local[best0], f_star)

    step_keys = jax.random.split(k_steps, cfg.inner_steps)
    r_all = jax.vmap(
        lambda k: jax.random.uniform(k, (cfg.num_particles, 3)))(step_keys)
    return S, V, f_local, S_star, f_star, r_all, k_gum


def _epoch_finish(S, S_star, f_star, f_trace, f_final, k_gum, Q, G, mask,
                  cfg: PSOConfig):
    """Epoch epilogue (one problem): projections, Ullmann refinement,
    feasibility, elite consensus — everything downstream of the fused
    inner loop, as ONE ``KernelBackend.epoch_finish`` launch. Returns
    the ``(carry, outs)`` pair ``run_epoch`` has always returned.

    ``f_final`` is the fused epoch kernel's last-step per-particle
    fitness (already in ``_fitness``'s scaled float units on both the
    float and quantized paths) threaded through instead of recomputed —
    the pre-fusion epilogue paid a full ``_fitness(S)`` launch for
    values the inner loop had just produced, bitwise-identically
    (``tests/test_backend.py::test_run_epoch_bitwise_equals_legacy_scan``).

    Two complementary projections are tried per particle:
      (a) adjacency-guided constructive (structured_project) — wins on
          sparse engine meshes where structure-blind argmax almost never
          lands on a consistent sub-DAG; optionally Gumbel-perturbed
          (τ-scaled noise on log S makes the constructive argmax a
          per-row softmax sample, so consensus-collapsed particles
          explore distinct assignments; τ=0 is exact deterministic
          projection);
      (b) plain greedy argmax + Ullmann candidate refinement — wins on
          dense targets where the constructive greedy can dead-end.
    """
    bk = kernel_backend.for_config(cfg)
    # The Gumbel field is the one random input of the epilogue; drawing
    # it host-side (same key, same shape, same dtype as the pre-fusion
    # code) keeps the kernel deterministic AND the RNG stream bitwise
    # identical to the legacy epilogue.
    if cfg.gumbel_tau > 0:
        gum = jax.random.gumbel(k_gum, S.shape, dtype=jnp.float32)
    else:
        gum = None
    M_hat, feasible, S_bar = bk.epoch_finish(
        S, f_final, gum, mask, Q, G, gumbel_tau=cfg.gumbel_tau,
        refine_threshold=cfg.refine_threshold,
        refine_iters=cfg.refine_iters, elite_k=elite_k_for(cfg),
        consensus_temp=cfg.consensus_temp)

    out = dict(mappings=M_hat, feasible=feasible, fitness=f_final,
               f_star_trace=f_trace, S_final=S)
    return (S_star, f_star, S_bar), out


def run_epoch(carry, key, Q, G, mask, cfg: PSOConfig):
    """One epoch of Algorithm 1 for a local swarm. carry holds the global
    controller state (S*, f*, S̄) persisted across epochs.

    The whole epoch is TWO kernel launches with no host-visible
    intermediates: the K-step inner loop through the seam's fused epoch
    kernel (``KernelBackend.epoch_fused`` — particle state VMEM-resident
    for the whole epoch on the Pallas path), then the entire epilogue
    (projections, Ullmann refinement, feasibility, elite consensus)
    through the fused tail (``KernelBackend.epoch_finish``). The ``ref``
    path is the original loose code, bitwise-equal
    (``tests/test_backend.py``).
    """
    bk = kernel_backend.for_config(cfg)
    S_bar = carry[2]
    S, V, f_local, S_star, f_star, r_all, k_gum = _epoch_start(
        carry, key, Q, G, mask, cfg)
    S, S_star, f_star, f_trace, f_last = bk.epoch_fused(
        S, V, S, f_local, S_star, f_star, S_bar, mask, Q, G, r_all,
        omega=cfg.omega, c1=cfg.c1, c2=cfg.c2, c3=cfg.c3,
        v_max=cfg.v_max, quantized=cfg.quantized)
    return _epoch_finish(S, S_star, f_star, f_trace, f_last, k_gum, Q, G,
                         mask, cfg)


def run_epoch_batch(carry, keys, Qb, Gb, maskb, cfg: PSOConfig):
    """Problem-batched ``run_epoch``: P problems, one fused-epoch launch.

    Equivalent to ``vmap(run_epoch)`` over the leading problem axis —
    the prologue is literally that vmap — but both the inner loop
    (``KernelBackend.epoch_fused_batch``) and the entire epilogue
    (``KernelBackend.epoch_finish_batch``) go through problem-gridded
    kernels, so one epoch over P problems is exactly two launches.
    Used by ``match_batch`` and the problem-sharded mesh matcher.
    """
    bk = kernel_backend.for_config(cfg)
    S_bar_b = carry[2]
    S, V, f_local, S_star, f_star, r_all, k_gum = jax.vmap(
        lambda c, k, Q, G, mk: _epoch_start(c, k, Q, G, mk, cfg)
    )(carry, keys, Qb, Gb, maskb)
    S, S_star, f_star, f_trace, f_last = bk.epoch_fused_batch(
        S, V, S, f_local, S_star, f_star, S_bar_b, maskb, Qb, Gb, r_all,
        omega=cfg.omega, c1=cfg.c1, c2=cfg.c2, c3=cfg.c3,
        v_max=cfg.v_max, quantized=cfg.quantized)
    f_final = f_last
    # Per-problem Gumbel fields, drawn from the same per-problem keys the
    # single-problem path uses so batch ≡ vmap(run_epoch) stays bitwise.
    if cfg.gumbel_tau > 0:
        gum = jax.vmap(
            lambda k, s: jax.random.gumbel(k, s.shape, dtype=jnp.float32)
        )(k_gum, S)
    else:
        gum = None
    M_hat, feasible, S_bar = bk.epoch_finish_batch(
        S, f_final, gum, maskb, Qb, Gb, gumbel_tau=cfg.gumbel_tau,
        refine_threshold=cfg.refine_threshold,
        refine_iters=cfg.refine_iters, elite_k=elite_k_for(cfg),
        consensus_temp=cfg.consensus_temp)
    out = dict(mappings=M_hat, feasible=feasible, fitness=f_final,
               f_star_trace=f_trace, S_final=S)
    return (S_star, f_star, S_bar), out


def default_carry(mask: jax.Array):
    """Cold-start controller state: uniform S̄ over the mask, no best yet.

    This is what every ``match`` call used before warm-starting existed;
    the online service replaces it with the previous epoch's consensus for
    repeat (workload, platform-state) arrivals.
    """
    maskf = mask.astype(jnp.float32)
    mask_rows = maskf.sum(-1, keepdims=True)
    S_bar0 = maskf / jnp.maximum(mask_rows, 1.0)
    return (S_bar0, jnp.float32(-jnp.inf), S_bar0)


def carry_fast_path(carry0, Q, G, mask, cfg: PSOConfig):
    """Trust-but-verify the warm-start carry (§warm starts, microsecond
    decisions): project the carried global best S* once and, if the result
    is still a feasible mapping of this problem, the whole epoch scan can
    be skipped — the previous decision is simply re-validated at the cost
    of ONE structured projection instead of a swarm launch.

    The cold prior (f* = -inf) never fast-paths, so cold calls are
    bit-identical with or without the flag. Returns ``(M_c, ok)``.
    """
    bk = kernel_backend.for_config(cfg)
    S_star0, f_star0, _ = carry0
    M_c = bk.structured_project(S_star0, Q, G, mask).astype(jnp.uint8)
    ok = (bk.is_feasible(M_c, Q, G)
          & (f_star0 > jnp.float32(-jnp.inf))
          & (f_star0 >= cfg.early_exit_fitness))
    return M_c, ok


def rebase_carry(carry, mask: jax.Array):
    """Project a stored controller carry onto a (possibly different)
    compatibility mask.

    The similarity-keyed carry store (service Tier 1) reuses the carry of
    the *nearest* platform state when the free-engine set has drifted:
    S* and S̄ are masked to the new compatibility mask and row-renormalized
    (rows whose support vanished fall back to uniform over the new mask).
    Row renormalization is a positive per-row scale, so for an *identical*
    mask the rebase is exactly the identity on any swarm-produced carry —
    Tier 0 and Tier 1 can therefore share one revalidation kernel.

    f* is passed through untouched: it is only ever used as a "this carry
    holds a real decision" gate (> -inf); fitness values are not
    comparable across platform states, so the caller decides what f to
    store after revalidation (see ``revalidate_carry``).
    """
    S_star, f_star, S_bar = carry
    maskf = mask.astype(jnp.float32)
    mask_rows = maskf.sum(-1, keepdims=True)
    uniform = maskf / jnp.maximum(mask_rows, 1.0)

    def onto(S):
        S = S.astype(jnp.float32) * maskf
        row = S.sum(-1, keepdims=True)
        return jnp.where(row > 1e-9, S / jnp.maximum(row, 1e-9), uniform)

    return onto(S_star), f_star, onto(S_bar)


def revalidate_carry(carry0, Q, G, mask, cfg: PSOConfig):
    """Tier-0/1 decision kernel: rebase + ONE masked structured projection.

    The batched pipeline's cheap stage: the carry is rebased onto this
    problem's (pruned) mask, its S* is projected once, and the projection
    is feasibility-checked against the *actual* Q/G — a rebased carry can
    therefore never yield an infeasible mapping marked found. Also
    computes the projected mapping's own fitness ``f_c`` on THIS problem
    (the stored f* is not transferable across platform states), which the
    service stores back on a Tier-1 hit.

    Returns ``dict(mapping, ok, ok_rebase, fitness, S_star, S_bar)``:
    ``ok`` is the Tier-0 verdict (carried-f* gate, bit-compatible with
    ``carry_fast_path``), ``ok_rebase`` the stricter Tier-1 verdict
    (also requires the projection's own fitness to clear the bound), and
    S_star/S_bar are the rebased controller state (f* intentionally
    omitted: hits store ``fitness``, swarm seeds reset it to -inf).
    """
    bk = kernel_backend.for_config(cfg)
    S_rb, f_star0, S_bar_rb = rebase_carry(carry0, mask)
    M_c = bk.structured_project(S_rb, Q, G, mask).astype(jnp.uint8)
    f_c = _fitness(M_c.astype(jnp.float32)[None], Q, G, cfg)[0]
    # ``ok`` gates on the CARRIED f* exactly like the in-kernel
    # ``carry_fast_path``, so Tier-0 batch revalidation and a single
    # warm ``match`` agree at any ``early_exit_fitness`` threshold.
    ok = (bk.is_feasible(M_c, Q, G)
          & (f_star0 > jnp.float32(-jnp.inf))
          & (f_star0 >= cfg.early_exit_fitness))
    # Tier 1 must not trust a fitness measured on a different platform
    # state: a REBASED carry additionally clears the bound with the
    # projection's own fitness on THIS problem.
    ok_rebase = ok & (f_c >= cfg.early_exit_fitness)
    return dict(mapping=M_c, ok=ok, ok_rebase=ok_rebase, fitness=f_c,
                S_star=S_rb, S_bar=S_bar_rb)


def _revalidate_batch_body(Qb: jax.Array, Gb: jax.Array, maskb: jax.Array,
                           cfg: PSOConfig, carry0):
    """Batched revalidation: B carries re-validated in one launch, no
    epochs — one projection + feasibility check per problem. Masks are
    pre-pruned exactly as ``_match_batch_body`` does, so the projection
    sees the same candidate sets the swarm that produced the carry saw."""
    B = maskb.shape[0]
    bk = kernel_backend.for_config(cfg)
    if cfg.prune_mask:
        maskb, prune_sweeps = bk.prune_fixpoint_batch(maskb, Qb, Gb,
                                                      cfg.prune_iters)
    else:
        prune_sweeps = jnp.zeros((B,), jnp.int32)
    outs = jax.vmap(
        lambda c, Q, G, mk: revalidate_carry(c, Q, G, mk, cfg)
    )(carry0, Qb, Gb, maskb)
    outs["prune_sweeps"] = prune_sweeps
    # echo the carried f* through the launch: the service reads the
    # stored-fitness of Tier-0 hits from the (single) batched output
    # fetch instead of a per-item host sync, and the echo stays valid
    # even when the stacked carry input buffers were donated to XLA
    outs["f_carry"] = jnp.asarray(carry0[1], jnp.float32)
    return outs


_revalidate_batch_impl = functools.partial(
    jax.jit, static_argnames=("cfg",))(_revalidate_batch_body)


def revalidate_batch(Qb: jax.Array, Gb: jax.Array, maskb: jax.Array,
                     cfg: PSOConfig, carry0):
    """Tier-0 pipeline entry point: batch-revalidate B stored carries.

    Inputs are stacked on a leading problem axis like ``match_batch``;
    ``carry0`` holds the per-problem carries to re-validate (exact warm
    carries for Tier 0, nearest-neighbour carries for Tier 1 — the rebase
    inside makes both cases one kernel). Returns a pytree of
    ``mapping`` (B, n, m) uint8, ``ok`` (B,) bool, ``fitness`` (B,) f32,
    the rebased ``S_star``/``S_bar``, and ``f_carry`` (B,) f32 — the
    carried f* echoed through the launch so callers can read it from the
    output fetch even after donating the carry input buffers. Cost is
    one jit dispatch and one projection per problem — no swarm, no
    epochs.
    """
    return _revalidate_batch_impl(Qb, Gb, maskb, cfg, carry0)


def _skip_epoch_outs(carry, n, m, cfg: PSOConfig):
    """Shape-matched placeholder outputs for an early-exited epoch."""
    _, f_star, _ = carry
    return dict(
        mappings=jnp.zeros((cfg.num_particles, n, m), jnp.uint8),
        feasible=jnp.zeros((cfg.num_particles,), bool),
        fitness=jnp.full((cfg.num_particles,), -jnp.inf, jnp.float32),
        f_star_trace=jnp.full((cfg.inner_steps,), f_star, jnp.float32))


def epoch_found(outs, cfg: PSOConfig) -> jax.Array:
    """Early-exit predicate: some particle projected to a feasible mapping
    whose fitness clears the bound."""
    return jnp.any(outs["feasible"]
                   & (outs["fitness"] >= cfg.early_exit_fitness))


def scan_epochs(run_one, carry0, keys, n, m, cfg: PSOConfig,
                all_found=None, done0=None):
    """Scan ``run_one(carry, k) -> (carry, outs)`` over the epoch keys,
    optionally gated by ``cfg.early_exit`` (skipped epochs cost one
    predicated branch and emit shape-matched empty outputs).

    ``run_one`` must drop the ``S_final`` entry from its outputs.
    ``all_found`` (distributed matcher) fuses the local found-predicate
    across the mesh so every shard takes the same branch — the predicate
    must be replicated or the collectives inside ``run_one`` deadlock.
    ``done0`` pre-marks the problem as solved before any epoch runs (the
    warm-carry fast path); it must likewise be replicated.

    Returns ``(carry, outs, epochs_run)``.
    """
    if not cfg.early_exit:
        carry, outs = jax.lax.scan(run_one, carry0, keys)
        return carry, outs, jnp.int32(cfg.epochs)

    def epoch_step(state, k):
        carry, done_prev, n_run = state

        def live(_):
            return run_one(carry, k)

        def skip(_):
            return carry, _skip_epoch_outs(carry, n, m, cfg)

        carry2, outs = jax.lax.cond(done_prev, skip, live, None)
        found = epoch_found(outs, cfg)
        if all_found is not None:
            found = all_found(found)
        done = done_prev | found
        n_run = n_run + (~done_prev).astype(jnp.int32)
        return (carry2, done, n_run), outs

    state0 = (carry0,
              jnp.bool_(False) if done0 is None else done0,
              jnp.int32(0))
    (carry, _, epochs_run), outs = jax.lax.scan(epoch_step, state0, keys)
    return carry, outs, epochs_run


def _match_body(key: jax.Array, Q: jax.Array, G: jax.Array, mask: jax.Array,
                cfg: PSOConfig, carry0):
    n, m = mask.shape
    if cfg.prune_mask:
        mask, prune_sweeps = kernel_backend.for_config(cfg).prune_fixpoint(
            mask, Q, G, cfg.prune_iters)
    else:
        prune_sweeps = jnp.int32(0)
    keys = jax.random.split(key, cfg.epochs)

    if cfg.early_exit and cfg.carry_fastpath:
        M_c, carry_ok = carry_fast_path(carry0, Q, G, mask, cfg)
    else:
        M_c = jnp.zeros((n, m), jnp.uint8)
        carry_ok = jnp.bool_(False)

    def run_one(carry, k):
        carry, outs = run_epoch(carry, k, Q, G, mask, cfg)
        del outs["S_final"]  # only needed by the distributed consensus
        return carry, outs

    (S_star, f_star, S_bar), outs, epochs_run = scan_epochs(
        run_one, carry0, keys, n, m, cfg, done0=carry_ok)
    outs["S_star"] = S_star
    outs["f_star"] = f_star
    outs["S_bar"] = S_bar
    outs["epochs_run"] = epochs_run
    outs["carry_mapping"] = M_c
    outs["carry_feasible"] = carry_ok
    outs["prune_sweeps"] = prune_sweeps
    return outs


# ---------------------------------------------------------------------------
# Batched problem axis B (coalesced concurrent arrivals)
# ---------------------------------------------------------------------------

def default_carry_batch(maskb: jax.Array):
    """Cold controller state for a stacked (B, n, m) mask batch."""
    return jax.vmap(default_carry)(maskb)


def scan_epochs_batch(run_one, carry0, keys, n, m, cfg: PSOConfig,
                      done0=None):
    """Batched-problem variant of ``scan_epochs``.

    ``run_one(carry_b, keys_b) -> (carry_b, outs_b)`` runs one epoch for
    every problem in the batch (all leaves carry a leading problem axis B;
    ``keys`` is (T, B) epoch keys). Early exit is *per problem*: a problem
    that already found a mapping has its carry frozen and its outputs
    replaced by the shape-matched skip placeholders — exactly what the
    single-problem ``scan_epochs`` skip branch produces — so one finished
    problem never stalls or perturbs the rest of the batch. Whole-batch
    compute is only skipped (one predicated branch) once *every* problem
    is done.

    Returns ``(carry, outs, epochs_run)`` with ``epochs_run`` shaped (B,).
    """
    B = jax.tree_util.tree_leaves(carry0)[0].shape[0]
    if not cfg.early_exit:
        carry, outs = jax.lax.scan(run_one, carry0, keys)
        return carry, outs, jnp.full((B,), cfg.epochs, jnp.int32)

    skip_outs_b = jax.vmap(lambda c: _skip_epoch_outs(c, n, m, cfg))

    def epoch_step(state, k_b):
        carry, done_prev, n_run = state

        def live(_):
            carry2, outs = run_one(carry, k_b)
            # freeze finished problems: keep their old carry, emit the
            # same placeholder outputs the single-problem skip branch does
            def keep(old, new):
                d = done_prev.reshape((B,) + (1,) * (new.ndim - 1))
                return jnp.where(d, old, new)
            carry2 = jax.tree_util.tree_map(keep, carry, carry2)
            outs = jax.tree_util.tree_map(keep, skip_outs_b(carry), outs)
            return carry2, outs

        def skip(_):
            return carry, skip_outs_b(carry)

        carry2, outs = jax.lax.cond(jnp.all(done_prev), skip, live, None)
        found = jax.vmap(lambda o: epoch_found(o, cfg))(outs)
        done = done_prev | found
        n_run = n_run + (~done_prev).astype(jnp.int32)
        return (carry2, done, n_run), outs

    state0 = (carry0,
              jnp.zeros((B,), bool) if done0 is None else done0,
              jnp.zeros((B,), jnp.int32))
    (carry, _, epochs_run), outs = jax.lax.scan(epoch_step, state0, keys)
    return carry, outs, epochs_run


def _match_batch_body(keys: jax.Array, Qb: jax.Array, Gb: jax.Array,
                      maskb: jax.Array, cfg: PSOConfig, carry0):
    """Algorithm 1 vmapped over a leading problem axis B.

    ``keys`` is (B,) PRNG keys — one per problem, split per problem into
    epoch keys so problem b consumes exactly the key stream a sequential
    ``match(keys[b], ...)`` would.
    """
    B, n, m = maskb.shape
    bk = kernel_backend.for_config(cfg)
    if cfg.prune_mask:
        maskb, prune_sweeps = bk.prune_fixpoint_batch(maskb, Qb, Gb,
                                                      cfg.prune_iters)
    else:
        prune_sweeps = jnp.zeros((B,), jnp.int32)
    # (B, T) epoch keys -> (T, B) for the scan
    epoch_keys = jax.vmap(lambda k: jax.random.split(k, cfg.epochs))(keys)
    epoch_keys = jnp.swapaxes(epoch_keys, 0, 1)

    if cfg.early_exit and cfg.carry_fastpath:
        M_c, carry_ok = jax.vmap(
            lambda c, Q, G, mk: carry_fast_path(c, Q, G, mk, cfg)
        )(carry0, Qb, Gb, maskb)
    else:
        M_c = jnp.zeros((B, n, m), jnp.uint8)
        carry_ok = jnp.zeros((B,), bool)

    def run_one(carry, k_b):
        carry, outs = run_epoch_batch(carry, k_b, Qb, Gb, maskb, cfg)
        del outs["S_final"]  # only needed by the distributed consensus
        return carry, outs

    (S_star, f_star, S_bar), outs, epochs_run = scan_epochs_batch(
        run_one, carry0, epoch_keys, n, m, cfg, done0=carry_ok)
    outs["S_star"] = S_star
    outs["f_star"] = f_star
    outs["S_bar"] = S_bar
    outs["epochs_run"] = epochs_run
    outs["carry_mapping"] = M_c
    outs["carry_feasible"] = carry_ok
    outs["prune_sweeps"] = prune_sweeps
    return outs


# Module-level jitted entry point (cfg is static). The online
# ``MatcherService`` builds its *own* per-bucket jit wrappers around
# ``_match_body`` so cached executables have a bounded, evictable lifetime.
_match_impl = functools.partial(jax.jit, static_argnames=("cfg",))(_match_body)

_match_batch_impl = functools.partial(jax.jit, static_argnames=("cfg",))(
    _match_batch_body)


def match_batch(keys: jax.Array, Qb: jax.Array, Gb: jax.Array,
                maskb: jax.Array, cfg: PSOConfig, carry0=None):
    """Batched Algorithm 1: B problems solved in one dispatch.

    Inputs are stacked on a leading problem axis: ``keys`` (B,) PRNG keys,
    ``Qb`` (B, n, n), ``Gb`` (B, m, m), ``maskb`` (B, n, m); ``carry0``
    optionally warm-starts each problem with its own ``(S*, f*, S̄)``
    (stack per-problem carries; ``None`` is the cold prior for all).

    Returns the ``match`` output pytree with a problem axis after the
    epoch axis: mappings (T, B, N, n, m), feasible/fitness (T, B, N),
    f_star_trace (T, B, K), S_star (B, n, m), f_star (B,), S_bar
    (B, n, m), epochs_run (B,) — each problem's slice equals what an
    independent ``match(keys[b], ...)`` returns (per-problem early exit
    included).
    """
    if carry0 is None:
        carry0 = default_carry_batch(jnp.asarray(maskb))
    return _match_batch_impl(keys, Qb, Gb, maskb, cfg, carry0)


def match(key: jax.Array, Q: jax.Array, G: jax.Array, mask: jax.Array,
          cfg: PSOConfig, carry0=None):
    """Single-device Algorithm 1: T epochs × N particles.

    ``carry0`` optionally warm-starts the global controller state with a
    previous call's ``(S_star, f_star, S_bar)`` for the same problem
    (see ``MatchResult.carry`` / the online ``MatcherService``); ``None``
    is the cold uniform prior.

    Returns a dict with per-epoch stacked results:
      mappings  (T, N, n, m) uint8
      feasible  (T, N) bool
      fitness   (T, N) f32
      f_star_trace (T, K) f32   — global-best trajectory (Fig. 2b)
      S_star/f_star/S_bar       — final controller state (warm-start carry)
      epochs_run                — epochs actually executed (< T under
                                  ``cfg.early_exit``)
    """
    if carry0 is None:
        carry0 = default_carry(mask)
    return _match_impl(key, Q, G, mask, cfg, carry0)


def best_feasible(outs):
    """Each problem's served mapping, selected from a batched epoch trace
    on the device (jit-able).

    ``outs`` holds ``feasible``/``fitness`` (T, B, N) and ``mappings``
    (T, B, N, n, m), as ``match_batch`` returns them, and optionally the
    fast path's ``carry_mapping`` (B, n, m) and ``carry_feasible`` (B,).
    Per problem the winner is the highest-fitness feasible particle over
    all epochs, chosen by ``np.argmax``'s rule over the feasible entries
    in epoch-major (T, N) order, as ``matcher.collect_result`` does: the
    first among ties, a feasible particle at -inf still ahead of every
    infeasible one, and the first feasible NaN ahead of everything. With
    no feasible particle, a carry the fast path verified serves its
    ``carry_mapping``.

    Returns ``(mapping, found)``: (B, n, m) uint8, zero where nothing was
    found, and (B,) bool.
    """
    feas = jnp.asarray(outs["feasible"])
    T, B, N = feas.shape
    feas = jnp.swapaxes(feas, 0, 1).reshape(B, T * N)
    fit = jnp.swapaxes(jnp.asarray(outs["fitness"], jnp.float32),
                       0, 1).reshape(B, T * N)
    nan = feas & jnp.isnan(fit)
    top = jnp.max(jnp.where(feas, fit, -jnp.inf), axis=1, keepdims=True)
    win = jnp.where(jnp.any(nan, axis=1, keepdims=True), nan,
                    feas & (fit == top))
    idx = jnp.argmax(win, axis=1)          # first winner
    best = jnp.asarray(outs["mappings"])[idx // N, jnp.arange(B), idx % N]
    found = jnp.any(feas, axis=1)
    carry_ok = outs.get("carry_feasible")
    if carry_ok is not None:
        best = jnp.where((carry_ok & ~found)[:, None, None],
                         outs["carry_mapping"], best)
        found = found | carry_ok
    return jnp.where(found[:, None, None], best, 0).astype(jnp.uint8), found


def served_outs(outs):
    """A ``match_batch`` output pytree as the service's swarm program
    returns it: the per-particle trace ``mappings`` and the fast path's
    ``carry_mapping`` give way to each problem's served ``mapping``
    (B, n, m) and ``found`` (B,) from :func:`best_feasible`; every other
    leaf passes through."""
    outs = dict(outs)
    outs["mapping"], outs["found"] = best_feasible(outs)
    del outs["mappings"], outs["carry_mapping"]
    return outs

"""The comparison that decides ``correct``: what the timed path produced
against the plain reference of the configuration's architecture.

The architecture's module (``bench/arch/<architecture>.py``, found by the
configuration's ``"architecture"``) gives both sides of the comparison:
the seeded weights, which the program's compression took at set-up and the
reference takes again here, and the plain reference
(``bench/reference/<architecture>.py``), which imports nothing of the
program. Nothing here depends on the architecture.

Serving: once the window has closed and the program's state is freed, a
sample of the requests the window served, drawn from the seed and always
holding the one with the most served tokens, is run through the reference
once per request: the prompt followed by the served tokens,
teacher-forced. At each served position the reference's logits give the
gap by which the served token lies below the reference's best token;
greedy decoding that agrees with the reference up to rounding keeps it
near 0. Two numbers come of it, each compared with the configuration's
limit: the widest gap and the mean gap over the sample's served tokens.
The control (``quant="fp8"``) reads, at the same positions, the gap of the
token that the reference in float8 puts first.

Training: the reference's first Adam steps on the same batches
(``reference/adam.py``), compared leaf by leaf with the program's
trainable leaves, whatever layer groups hold them.
"""
from __future__ import annotations

import numpy as np

from bench import arch as arch_lib


def sample_requests(served, seed: int, target_tokens) -> list[int]:
    """Indices of served requests: the longest first, then others in a
    seeded order until ``target_tokens`` served tokens are covered (every
    served request when ``target_tokens`` is None)."""
    if not served:
        return []
    if target_tokens is None:
        return list(range(len(served)))
    lens = np.array([len(t) for _, t in served])
    longest = int(np.argmax(lens))
    order = np.random.default_rng([int(seed), 11]).permutation(len(served))
    picked, total = [longest], int(lens[longest])
    for i in order:
        if total >= target_tokens:
            break
        if i != longest:
            picked.append(int(i))
            total += int(lens[i])
    return picked


def logit_gaps(cfg: dict, seed: int, served, picked, max_seq_len: int,
               max_out: int, control: bool = False) -> dict:
    """Gaps of the served tokens below the reference's best: the widest
    and the mean. With ``control``, the same numbers of the float8
    control."""
    import jax
    import jax.numpy as jnp
    arch = arch_lib.of(cfg)
    weights = arch.weights(cfg, seed)
    widest = {"": 0.0, "control_": 0.0}
    total = {"": 0.0, "control_": 0.0}
    n_tok = 0

    def gaps_of(ref, best, pick):
        got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return best - got

    for i in picked:
        prompt, toks = served[i]
        p, n = len(prompt), len(toks)
        seq = np.zeros(max_seq_len, np.int32)
        seq[:p] = prompt
        seq[p:p + n] = toks
        at = np.full(max_out, p - 1, np.int32)
        at[:n] = np.arange(p - 1, p - 1 + n)
        with jax.default_matmul_precision("highest"):
            ref = arch.reference.logits_at(weights, cfg, seq, p + n, at)[:n]
            best = jnp.max(ref, axis=-1)
            picks = {"": jnp.asarray(toks, jnp.int32)}
            if control:
                picks["control_"] = jnp.argmax(arch.reference.logits_at(
                    weights, cfg, seq, p + n, at, quant="fp8")[:n], axis=-1)
            for key, pick in picks.items():
                g = gaps_of(ref, best, pick)
                widest[key] = max(widest[key], float(jnp.max(g)))
                total[key] += float(jnp.sum(g))
        n_tok += n
    n = max(n_tok, 1)
    out = {"logit_gap": widest[""], "mean_logit_gap": total[""] / n,
           "n_tokens": n_tok, "n_requests": len(picked)}
    if control:
        out["control_logit_gap"] = widest["control_"]
        out["control_mean_logit_gap"] = total["control_"] / n
    return out


def _ref_name(name: str) -> tuple[str, bool]:
    """The reference leaf of a program trainable leaf, and whether the two
    hold the same elements in another layout (a BCSR block store). A block
    store is keyed by its path in the tree, joined by ``/``
    (``['bcsr_data']['layers/<group>/attn/wq']``), under any group."""
    if name.startswith("['bcsr_data']"):
        path = name[len("['bcsr_data']['"):-2].split("/")
        return "".join(f"['{p}']" for p in path), True
    return name[len("['dense']"):], False


def program_leaves(leaves, grad1, change, ref_shapes: dict) -> dict:
    """The program's per-leaf norms keyed by reference leaf. A compressed
    projection leaves a placeholder in the dense residue: it has no
    reference leaf of its shape and is skipped."""
    out = {}
    for (name, shape), g, d in zip(leaves, grad1, change):
        ref, relaid = _ref_name(name)
        if ref in ref_shapes and (relaid or ref_shapes[ref] == tuple(shape)):
            out[ref] = (g, d)
    return out


def reference_steps(cfg: dict, mix: dict, seed: int, batches,
                    quant=None) -> dict:
    """The reference's first steps from the seed's weights: losses, and
    per leaf the first gradient's norm and the change's norm."""
    import jax
    from bench.reference import adam
    arch = arch_lib.of(cfg)
    z = float(mix["loss"]["z_weight"])

    def grad_fn(w, b):
        with jax.default_matmul_precision("highest"):
            return arch.reference.loss_and_grad(
                w, cfg, b["inputs"], b["labels"], z, quant=quant)

    return adam.steps(arch.weights(cfg, seed), grad_fn, batches,
                      float(mix["optimizer"]["lr"]))


def train_gaps(losses, leaves: dict, ref: dict) -> dict:
    """Worst relative gaps between a run's first steps and the
    reference's: each step's loss; each leaf's first-gradient norm and its
    change over the steps (the gap between the norms, not the norm of the
    difference, over the larger of the leaf's reference norm and the
    median leaf's). Leaves whose reference gradient is under a thousandth
    of the median leaf's move by round-off alone and are left out of the
    change."""
    pairs = [(leaves[k], ref["leaves"][k]) for k in sorted(leaves)]
    med_g = float(np.median([r[0] for _, r in pairs]))
    moved = [(a, r) for a, r in pairs if r[0] >= 1e-3 * med_g]
    med_d = float(np.median([r[1] for _, r in moved]))
    return {
        "loss_gap": max(abs(a - r) / abs(r)
                        for a, r in zip(losses, ref["losses"])),
        "grad_norm_gap": max(abs(a[0] - r[0]) / max(r[0], med_g)
                             for a, r in pairs),
        "change_norm_gap": max(abs(a[1] - r[1]) / max(r[1], med_d)
                               for a, r in moved),
        "n_leaves": len(pairs), "n_moved": len(moved),
    }

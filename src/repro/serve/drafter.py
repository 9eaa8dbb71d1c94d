"""Draft-token proposers for the speculative decode lane.

The verify step makes *any* drafter lossless — a wrong draft only costs
acceptance rate, never output correctness — so drafters are free to be
cheap and approximate.  Two flavours ship:

* :class:`NGramDrafter` (``kind="host"``) — prompt-lookup decoding: the
  last n-gram of the committed context (prompt + emitted tokens) is looked
  up at its most recent earlier occurrence and the tokens that followed it
  are proposed.  Zero model cost, pure host Python, and surprisingly
  effective whenever generation revisits prompt material or falls into
  loops (which untrained seed params reliably do — the reason synthetic
  traces get non-trivial acceptance).
* :class:`MTPDrafter` (``kind="model"``) — the DeepSeek-V3 multi-token-
  prediction head (``cfg.mtp``): a jitted batched beam over
  ``mtp_proj``/``mtp_layer`` that drafts a token tree for every slot at
  once from the last verify step's hidden carry
  (:func:`repro.models.transformer.mtp_draft_tree`).

``kind`` tells the engine how to call it: "host" drafters expose
``draft_tree(context, n, branch)`` per request (and ``draft(context, k)``,
the chain a replaying request's tail is padded with); "model" drafters
expose ``draft_tree_batch(params, hidden, token, pos) -> [n_slots, n]``
over the whole pool.

Drafts are ``(tokens, parents)`` trees in *draft space*: ``parents[i]`` is
the index of node i's parent among the drafted nodes, or -1 for a child of
the root (the last committed token — the engine holds window index 0 for
it).  Parents are topological (``parents[i] < i``) and siblings carry
distinct tokens, so the engine's accept walk is unambiguous.  A chain
(``branch=1``) is ``parents = [-1, 0, 1, ...]``.  Host drafters override
:meth:`draft_tree` (the base class falls back to a chain of
:meth:`draft`); the model drafter beams the MTP head into a static
chain-major topology.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def chain_parents(n: int) -> list[int]:
    """Draft-space parents of a linear chain: [-1, 0, 1, ...]."""
    return list(range(-1, n - 1))


def tree_depths_ancestors(parents: list[int]) -> tuple[list[int], list[int]]:
    """Window-space (depth, ancestor-bitmask) arrays for a draft tree.

    ``parents`` is draft-space (see module docstring); the returned lists
    have length ``len(parents) + 1`` and describe the *window*: entry 0 is
    the root (depth 0, anc bit 0), entry i+1 is draft node i at window
    index i+1 with bit i+1 OR'd onto its parent's mask — the operands
    :func:`repro.models.transformer.verify_step` takes.
    """
    depth = [0]
    anc = [1]
    for i, p in enumerate(parents):
        if not -1 <= p < i:
            raise ValueError(f"parents[{i}] = {p} is not topological")
        w = i + 1
        depth.append(depth[p + 1] + 1)
        anc.append(anc[p + 1] | (1 << w))
    return depth, anc


class Drafter:
    """Base: subclasses set ``kind`` ("host" | "model") and implement the
    matching draft method."""

    name = "base"
    kind = "host"

    def draft(self, context: list[int], k: int) -> list[int]:
        raise NotImplementedError

    def draft_tree(self, context: list[int], n: int,
                   branch: int) -> tuple[list[int], list[int]]:
        """(tokens, draft-space parents) with up to ``n`` nodes.  Default:
        the linear draft as a single chain — any drafter works in the tree
        lane unchanged; branching only raises acceptance."""
        return self.draft(context, n), chain_parents(n)

    def draft_tree_batch(self, params, hidden, token, pos):
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Prompt-lookup drafting: propose the continuation of the most recent
    earlier occurrence of the context's trailing n-gram (longest n first),
    falling back to repeat-last when nothing matches."""

    name = "ngram"
    kind = "host"

    def __init__(self, max_n: int = 3):
        if max_n < 1:
            raise ValueError("ngram drafter needs max_n >= 1")
        self.max_n = max_n

    def draft(self, context: list[int], k: int) -> list[int]:
        L = len(context)
        for n in range(min(self.max_n, L - 1), 0, -1):
            pat = context[-n:]
            for i in range(L - n - 1, -1, -1):
                if context[i:i + n] == pat:
                    cont = context[i + n:i + n + k]
                    if cont:
                        return (cont + [cont[-1]] * k)[:k]
        return [context[-1]] * k

    def _candidates(self, context: list[int], k: int,
                    branch: int) -> list[list[int]]:
        """Up to ``branch`` candidate continuations with distinct first
        tokens, in the same longest-n / most-recent-match preference order
        :meth:`draft` uses (so candidate 0 IS the linear draft's choice)."""
        L = len(context)
        out: list[list[int]] = []
        seen: set[int] = set()
        for n in range(min(self.max_n, L - 1), 0, -1):
            pat = context[-n:]
            for i in range(L - n - 1, -1, -1):
                if context[i:i + n] == pat:
                    cont = context[i + n:i + n + k]
                    if cont and cont[0] not in seen:
                        seen.add(cont[0])
                        out.append(cont)
                        if len(out) >= branch:
                            return out
        return out

    def draft_tree(self, context: list[int], n: int,
                   branch: int) -> tuple[list[int], list[int]]:
        """Branch on the top candidate continuations: the best match keeps
        a chain of the remaining budget (identical to the linear draft),
        and each runner-up (distinct first token) hangs one node off the
        root — covering the most likely divergence point, the first
        drafted token."""
        cands = self._candidates(context, n, max(1, branch))
        if not cands:
            return [context[-1]] * n, chain_parents(n)
        extras = cands[1:n]                     # keep >= 1 node for the chain
        main_len = n - len(extras)
        main = (cands[0] + [cands[0][-1]] * n)[:main_len]
        toks = list(main)
        parents = chain_parents(main_len)
        for c in extras:
            toks.append(c[0])
            parents.append(-1)
        return toks, parents


class MTPDrafter(Drafter):
    """Batched MTP-head drafting over the slot pool.  ``hidden`` is the
    post-``ln_f`` hidden at each slot's last committed position (zeros
    right after prefill — the head free-runs from the embedding there).
    :meth:`draft_tree_batch` beams the head: top-``branch`` first tokens
    each root a greedy chain (static chain-major topology exposed as
    :attr:`tree_parents`; ``branch=1`` is one greedy chain)."""

    name = "mtp"
    kind = "model"

    def __init__(self, cfg: ModelConfig, rt, n: int, branch: int):
        if not cfg.mtp:
            raise ValueError(
                f"{cfg.name} has no MTP head (cfg.mtp is False); "
                "use the ngram drafter")
        from repro.models import model as M
        from repro.models import transformer as T

        def mtp_draft_tree(p, h, t, pos):
            return M.mtp_draft_tree(p, cfg, h, t, pos, n, branch, rt)

        self._fn = jax.jit(mtp_draft_tree)
        parents: list[int] = []
        for clen in T.mtp_chain_lengths(n, branch):
            prev = -1
            for _ in range(clen):
                parents.append(prev)
                prev = len(parents) - 1
        self.tree_parents = parents

    def draft_tree_batch(self, params, hidden, token, pos):
        return self._fn(params, jnp.asarray(hidden),
                             jnp.asarray(token, jnp.int32),
                             jnp.asarray(pos, jnp.int32))


def make_drafter(spec: "str | Drafter | None", cfg: ModelConfig, rt,
                 n: int, branch: int) -> Drafter:
    """``"ngram" | "ngram:N" (max n-gram) | "mtp"`` or a built instance.
    ``n``/``branch`` (the engine's ``spec_tree``/``spec_branch``) pre-build
    the model drafter's beam topology."""
    if spec is None:
        return NGramDrafter()
    if isinstance(spec, Drafter):
        return spec
    name, _, arg = spec.partition(":")
    if name == "ngram":
        return NGramDrafter(max_n=int(arg)) if arg else NGramDrafter()
    if name == "mtp":
        return MTPDrafter(cfg, rt, n, branch)
    raise ValueError(f"unknown drafter {spec!r}; one of ['ngram', 'mtp']")

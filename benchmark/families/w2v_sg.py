"""The skip-gram word2vec family: the ``w2v`` family's model, inputs, conf
and table reads (``apps/w2v_main.py``'s sequence, every mechanism at its
default), with what is CBOW-shaped replaced: the first-step check and the
held-out loss lay a batch out as *pairs* and hold the program to
``reference/w2v_sg.py``.

With ``[word2vec] sg: 1`` the program draws ``K`` negatives for every
``(center, context)`` pair of the padded ``(B, 2W)`` grid
(``models/word2vec.py::_build_grads_sg`` draws ``(B, 2W, K)``), pulls
``v[context]`` and ``h[center], h[negatives]`` per pair and pushes a
gradient to each.  A chunk still counts **center words**, so ``words_per_s``
means here what it means in the CBOW cells and what word2vec.c's own
words/s means.

Every attribute of the program this file touches is listed in
``benchmark/README.md``, but one: ``Word2Vec.resolved_rendering`` (read
after the first step; anything but ``sg`` is refused).
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import traffic as traffic_gen
from ..reference import w2v_sg as reference
from .w2v import ROW_SAMPLE, Family as CBOWFamily, as_cbow


class Family(CBOWFamily):
    def _pair_layout(self, centers, contexts, mask, negs):
        """The ``(B, 2W)`` grid with its ``(B, 2W, K)`` negatives as
        ``B * 2W`` pairs over the distinct rows they name: ``t_ids``,
        ``t_valid`` (pairs, K+1), column 0 the center; ``c_ids``,
        ``c_valid`` (pairs,).  Dead pairs keep their rows in the sets, so
        the check also proves that they are written by nobody."""
        B, W2 = contexts.shape
        t_words = np.concatenate(
            [np.broadcast_to(centers[:, None, None], (B, W2, 1)), negs],
            axis=2).reshape(B * W2, -1)
        # a negative equal to its center is skipped (word2vec.h:584-586)
        t_valid = np.concatenate(
            [np.ones((B, W2, 1), bool), negs != centers[:, None, None]],
            axis=2).reshape(B * W2, -1)
        c_valid = mask.reshape(-1)
        t_valid &= c_valid[:, None]
        t_rows, t_ids = np.unique(self.slot_of[t_words], return_inverse=True)
        c_rows, c_ids = np.unique(self.slot_of[contexts.reshape(-1)],
                                  return_inverse=True)
        # bucketed, so that the reference's programs keep their shapes
        # (and their place in the compile cache) from seed to seed
        return (self._bucketed(t_rows), t_ids.reshape(t_words.shape),
                t_valid, self._bucketed(c_rows), c_ids.reshape(-1), c_valid)

    def first_step_check(self) -> dict:
        """Run the first train step as a one-step chunk and hold the rows
        it touched to the plain reference."""
        batch = self.batcher.peek("epoch", self.centers)
        centers, contexts, mask = as_cbow(batch, self.window)
        negs = self._negatives(
            self.model._key, (len(centers), 2 * self.window, self.negative))
        t_rows, t_ids, t_valid, c_rows, c_ids, c_valid = \
            self._pair_layout(centers, contexts, mask, negs)

        def touched():
            return {**self.rows(t_rows, ("h", "h2sum")),
                    **self.rows(c_rows, ("v", "v2sum"))}

        before = touched()
        t0 = time.perf_counter()
        _words, loss = self.run_chunk(1)
        train_call_s = time.perf_counter() - t0
        if self.batcher.last is not batch:
            raise RuntimeError("train() did not take the peeked batch: the "
                               "program's batch rule changed (README.md)")
        rendering = self.model.resolved_rendering
        if rendering != "sg":
            raise RuntimeError(f"the program chose the {rendering!r} "
                               "rendering: this family measures the "
                               "per-pair skip-gram step, 'sg'")
        want = reference.step(
            before, t_ids, t_valid, c_ids, c_valid, alpha=self.alpha,
            lr=float(self.config["server"]["initial_learning_rate"]))
        fields = reference.compare(touched(), want, before)
        # rows the whole run must have moved: the first step's valid
        # contexts (skip-gram's input rows)
        live = np.flatnonzero(np.bincount(
            c_ids[c_valid], minlength=len(c_rows)))[:ROW_SAMPLE]
        self.live_slots = c_rows[live]
        self.live_before = {"v": before["v"][live]}
        return {"ok": all(f["ok"] for f in fields.values())
                and np.isfinite(loss), "fields": fields, "loss": loss,
                "rows_checked": int(t_ids.max() + c_ids.max() + 2),
                "train_call_s": train_call_s,
                "sampler_max_abs_err": self.sampler_error()}

    def eval_loss(self):
        """(error, ns) of a held-out batch drawn from the seed, on the table
        as it stands (``reference.held_out_loss``): ``eval_centers`` centers
        with full windows, ``negative`` draws **a pair** from unigram^0.75
        of the vocabulary's counts."""
        rng = np.random.default_rng([self.seed, 0xE7A1])
        n, W, K = int(self.traffic["eval_centers"]), self.window, \
            self.negative
        pos = rng.integers(0, len(self.tokens), n)
        sent = np.searchsorted(self.offsets, pos, side="right") - 1
        lo, hi = self.offsets[sent], self.offsets[sent + 1]
        off = np.concatenate([np.arange(-W, 0), np.arange(1, W + 1)])
        ctx_pos = pos[:, None] + off[None, :]
        mask = (ctx_pos >= lo[:, None]) & (ctx_pos < hi[:, None])
        contexts = np.where(mask, self.tokens[np.clip(
            ctx_pos, 0, len(self.tokens) - 1)], 0)
        centers = self.tokens[pos]
        p = self.vocab.counts.astype(np.float64) ** 0.75
        negs = traffic_gen.draw_ranks(
            rng, p / p.sum(), n * 2 * W * K).reshape(n, 2 * W, K)
        t_rows, t_ids, t_valid, c_rows, c_ids, c_valid = \
            self._pair_layout(centers, contexts, mask, negs)
        return reference.held_out_loss(
            self.rows(t_rows, ("h",))["h"], self.rows(c_rows, ("v",))["v"],
            t_ids, t_valid, c_ids, c_valid, alpha=self.alpha)

"""Serving engine: batched prefill + decode with KV caches and the paper's
scan-based top-p (nucleus) sampler wired into the decode step (paper §5/§6.5 —
radix sort + prefix sum + inverse-transform sample, all on the matmul scan).
``sampler="topp_segmented"`` routes the same operator through the segmented
subsystem: the batch's logit rows become segments of one packed array, so a
ragged decode batch (rows of different active vocab slices, via
``sample_packed``) top-p samples in one launch without padding.
``scan_method=`` overrides the model config's scan method, so stateful decode
(the SSM/mLSTM linear-recurrence state updates, which route through
``repro.core.linrec.linear_scan``) can pick the fused kernel or blocked
pipeline without rebuilding the config by hand."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guards
from repro.core.dist_ops import dist_top_p_sample
from repro.core.primitives import METHODS, top_p_sample
from repro.core.segmented import SegmentedBatch, segment_top_p_sample
from repro.models.layers import ROW_TILE
from repro.models.model import build_model
from repro.utils.sharding import use_mesh


def sample_padded_rows(logits, u, *, p, temperature, method, sort_method,
                       bits_per_pass):
    """Top-p sample each row of ``logits`` (B, V) with its uniform ``u``
    (B, 1), on the rows padded to a multiple of ``layers.ROW_TILE``.

    The padding gives a row the same device layout, so the same rounding of
    its softmax and prefix sums, whether it is sampled alone or in a batch:
    a one-row array is laid out differently from a batch of rows on a TPU,
    and its reductions group the vocabulary differently.  Padded rows are
    zero logits and are dropped.
    """
    b = logits.shape[0]
    pad = -b % ROW_TILE
    tok = top_p_sample(jnp.pad(logits, ((0, pad), (0, 0))), None, p=p,
                       temperature=temperature, method=method,
                       sort_method=sort_method, bits_per_pass=bits_per_pass,
                       u=jnp.pad(u, ((0, pad), (0, 0))))
    return tok[:b].astype(jnp.int32)


class ServeEngine:
    SAMPLERS = ("greedy", "topp_auto", "topp_scan", "topp_kernel",
                "topp_blocked", "topp_segmented", "topp_sharded", "topp_xla")

    def __init__(self, cfg, params, *, mesh=None, max_len: int = 512,
                 top_p: float = 0.9, temperature: float = 1.0,
                 sampler: str = "topp_scan", bits_per_pass: int = 4,
                 scan_method: Optional[str] = None):
        sampler = guards.validate_choice(sampler, self.SAMPLERS,
                                         name="sampler", op="ServeEngine")
        # eager: fail at construction, not in jit
        bits_per_pass = guards.validate_bits_per_pass(bits_per_pass,
                                                      op="ServeEngine")
        guards.validate_probability(top_p, name="top_p", op="ServeEngine")
        guards.validate_temperature(temperature, op="ServeEngine")
        max_len = guards.validate_positive(max_len, name="max_len",
                                           op="ServeEngine")
        if scan_method is not None:
            if scan_method != "auto" and scan_method not in METHODS:
                raise ValueError(f"unknown scan_method {scan_method!r}; "
                                 f"expected one of {METHODS + ('auto',)}")
            cfg = dataclasses.replace(cfg, scan_method=scan_method)
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.max_len = max_len
        self.top_p = top_p
        self.temperature = temperature
        self.sampler = sampler
        # radix-2^k width of the sampler's sort passes: 4 -> the decode-path
        # bf16 key sort runs 4 radix-16 passes instead of 16 binary splits.
        self.bits_per_pass = bits_per_pass
        self.model = build_model(cfg)
        self._prefill = jax.jit(self._prefill_impl)
        if guards.checks_enabled():
            # checkified decode: staged guard_check assertions (pos < max_len)
            # fire as JaxRuntimeError.  checkify does not compose with donated
            # buffers, so this path re-uses the cache allocation instead.
            from jax.experimental import checkify
            cdec = jax.jit(checkify.checkify(self._decode_impl,
                                             errors=checkify.user_checks))

            def _decode_checked(params, caches, tok, pos, key):
                err, out = cdec(params, caches, tok, pos, key)
                err.throw()
                return out

            self._decode = _decode_checked
        else:
            self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))

    # ---- sampling (the paper's operator) ----
    def _sample(self, logits, key):
        """samplers: greedy | topp_auto (method from the tuning table) |
        topp_scan (matmul scans) | topp_kernel (fused Pallas radix passes +
        one-launch sampling tail) | topp_blocked (scans on the §4 blocked
        pipeline) | topp_segmented (rows packed as segments of one array,
        sampled by the segmented subsystem) | topp_sharded (model-parallel
        vocab: the distributed sampler over the mesh's "model" axis; on a
        mesh without that axis, or none at all, it degrades to the local
        matmul sampler — the same operator topp_scan runs) | topp_xla
        (baseline)."""
        if self.sampler == "greedy":
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if self.sampler == "topp_sharded":
            if (self.mesh is not None and "model" in self.mesh.shape
                    and self.mesh.shape["model"] > 1):
                return dist_top_p_sample(
                    logits, key, self.mesh, "model", p=self.top_p,
                    temperature=self.temperature, method="matmul",
                    bits_per_pass=self.bits_per_pass).astype(jnp.int32)
            u = jax.random.uniform(key, logits.shape[:-1] + (1,),
                                   jnp.float32)
            return sample_padded_rows(logits, u, p=self.top_p,
                                      temperature=self.temperature,
                                      method="matmul", sort_method="radix",
                                      bits_per_pass=self.bits_per_pass)
        if self.sampler == "topp_segmented":
            b, v = logits.shape
            offsets = jnp.arange(b + 1, dtype=jnp.int32) * v
            return segment_top_p_sample(
                logits.reshape(b * v), offsets, key, p=self.top_p,
                temperature=self.temperature,
                bits_per_pass=self.bits_per_pass).astype(jnp.int32)
        method = {"topp_kernel": "kernel", "topp_blocked": "blocked",
                  "topp_auto": "auto"}.get(self.sampler, "matmul")
        u = jax.random.uniform(key, logits.shape[:-1] + (1,), jnp.float32)
        return sample_padded_rows(
            logits, u, p=self.top_p, temperature=self.temperature,
            method=method,
            sort_method="xla" if self.sampler == "topp_xla" else "radix",
            bits_per_pass=self.bits_per_pass)

    def sample_packed(self, packed: SegmentedBatch, key) -> jnp.ndarray:
        """Top-p sample every segment of a packed ragged logits batch at once.

        ``packed``: a :class:`~repro.core.segmented.SegmentedBatch` whose
        segments are per-request logit slices (rows may have different
        lengths — e.g. per-request vocabulary masks in continuous batching).
        Returns one int32 segment-local token id per segment, in one launch;
        no padding to the longest row is performed.
        """
        return segment_top_p_sample(
            packed.values, packed.offsets, key, p=self.top_p,
            temperature=self.temperature,
            bits_per_pass=self.bits_per_pass).astype(jnp.int32)

    def _prefill_impl(self, params, batch, key):
        with use_mesh(self.mesh):
            last_logits, caches = self.model.prefill(params, batch,
                                                     cache_len=self.max_len)
            tok = self._sample(last_logits, key)
            return tok, caches

    def _decode_impl(self, params, caches, tok, pos, key):
        with use_mesh(self.mesh):
            guards.guard_check(lambda: pos < self.max_len,
                               "decode: pos must stay below max_len (the KV "
                               "cache budget) — raise max_len= at engine "
                               "construction")
            logits, caches = self.model.decode_step(params, tok[:, None],
                                                    caches, pos)
            new_tok = self._sample(logits, key)
            return new_tok, caches

    def generate(self, batch: Dict, max_new_tokens: int, key, *,
                 eos_id: Optional[int] = None,
                 sync_every: int = 8) -> jnp.ndarray:
        """Generate up to ``max_new_tokens`` tokens per row.

        ``batch``: model inputs incl. ``"tokens"`` (B, S).  Returns
        ``(B, new_tokens)`` int32 — ``new_tokens == max_new_tokens``, or
        fewer when ``eos_id`` is set and every row finished early
        (``max_new_tokens == 0`` returns an empty ``(B, 0)`` array without
        touching the model).

        Args:
            batch: Model inputs including ``"tokens"`` of shape (B, S).
            max_new_tokens: Number of tokens to decode (>= 0).
            key: PRNG key for the samplers.
            eos_id: Optional end-of-sequence token id.  Rows that emit it
                keep emitting it (their KV entries are not advanced with new
                content), and decoding stops once every row has finished.
            sync_every: How often (in tokens) the all-rows-done mask is
                synced to the host when ``eos_id`` is set — the scheduler
                tick.  The mask itself stays on device; a larger tick means
                fewer host round-trips but up to ``sync_every - 1`` wasted
                decode steps after the last row finishes.  The returned
                tokens are bit-identical for every ``sync_every >= 1``
                (over-decoded trailing columns are trimmed).

        Raises:
            ValueError: If ``max_new_tokens`` is negative, ``sync_every``
                is not positive, or the request does not fit the KV cache
                budget (``prompt_len + cache_offset + max_new_tokens >
                max_len``).
        """
        tokens = batch["tokens"]
        b, s = tokens.shape
        off = self.cfg.n_img_tokens if self.cfg.family == "vlm" else 0
        if max_new_tokens < 0:
            raise ValueError(
                f"generate: max_new_tokens must be >= 0, got {max_new_tokens}")
        sync_every = guards.validate_positive(sync_every, name="sync_every",
                                              op="generate")
        if s + off + max_new_tokens > self.max_len:
            raise ValueError(
                f"generate: prompt ({s} tokens) + cache offset ({off}) + "
                f"max_new_tokens ({max_new_tokens}) = "
                f"{s + off + max_new_tokens} overflows the KV cache budget "
                f"(max_len={self.max_len}); raise max_len= at engine "
                "construction or shorten the request")
        if max_new_tokens == 0:
            return jnp.zeros((b, 0), jnp.int32)
        key, k0 = jax.random.split(key)
        tok, caches = self._prefill(self.params, batch, k0)
        # the done mask lives on device; only jnp.all(done) crosses to the
        # host, and only once per sync_every-token scheduler tick
        done = (tok == eos_id) if eos_id is not None else None
        out = [tok]
        pos = s + off
        for i in range(max_new_tokens - 1):
            if (done is not None and i % sync_every == 0
                    and bool(jax.device_get(jnp.all(done)))):
                break  # every row emitted eos_id — stop early
            key, k = jax.random.split(key)
            tok, caches = self._decode(self.params, caches, tok,
                                       jnp.asarray(pos + i, jnp.int32), k)
            if done is not None:
                tok = jnp.where(done, jnp.asarray(eos_id, tok.dtype), tok)
                done = done | (tok == eos_id)
            out.append(tok)
        res = jnp.stack(out, axis=1)
        if done is not None and res.shape[1] > 1:
            # trim columns decoded past the point where every row had
            # finished — reproduces per-token early exit bit-identically
            # whatever the tick size
            col_done = np.logical_or.accumulate(
                np.asarray(res == eos_id), axis=1).all(axis=0)
            hits = np.nonzero(col_done)[0]
            if hits.size:
                res = res[:, :int(hits[0]) + 1]
        return res

"""Serving engine + launcher: batched prefill + decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --tiny \\
        --requests 16 --prompt-len 64 --gen 32

:class:`ServeEngine` is the importable core — one constructed engine is a
serving session (config resolved, sharding env built, params initialized,
prefill/decode steps jitted once) that :meth:`generate`\\ s batches on
demand. The workloads serving tier drives it in-process: attach one to a
``Service`` resource via ``WorkloadPlane.attach_engine`` and each
``…/invoke`` request lands in :meth:`infer`. ``main()`` is a thin argv
wrapper over the same object.

Drives the same prefill/decode step functions the dry-run lowers at
production shapes: a batch of synthetic prompts is prefilled (KV caches /
recurrent states built), then tokens are generated step by step. Reports
prefill and decode throughput. With ``--mesh``, runs sharded (incl. the
§Perf context-parallel cache via ``--ctx-parallel``).
"""

from __future__ import annotations

import argparse
import time
from typing import Optional


class ServeEngine:
    """One in-process serving session for an arch.

    Construction is the expensive part (params + jit); ``generate`` is
    the per-batch hot path, handling both LM (prefill → KV-cache decode)
    and encoder-decoder (encode → decode-state) branches.
    """

    def __init__(self, arch: str, tiny: bool = True,
                 mesh: Optional[str] = None, ctx_parallel: bool = False,
                 seed: int = 0):
        import jax
        from jax.sharding import AxisType

        from repro.configs import get_config, get_tiny_config
        from repro.launch.mesh import make_env
        from repro.launch.train import parse_mesh
        from repro.models import steps
        from repro.parallel import null_env, use_env

        self.arch = arch
        self.cfg = get_tiny_config(arch) if tiny else get_config(arch)
        mesh_shape = parse_mesh(mesh) if mesh is not None else None
        if mesh_shape is not None:
            m = jax.make_mesh(mesh_shape, ("data", "model"),
                              axis_types=(AxisType.Auto,) * 2)
            overrides = {"kv_seq": "model"} if ctx_parallel else {}
            self.env = make_env(m, overrides=overrides)
        else:
            self.env = null_env()
        self._use_env = use_env
        self._key = jax.random.key(seed)
        with use_env(self.env):
            self.params = steps.init_params(self.cfg, self._key)
            if not self.cfg.is_encoder_decoder:
                self._prefill = jax.jit(steps.make_prefill_step(self.cfg))
            self._decode = jax.jit(steps.make_decode_step(self.cfg))

    # -- the per-batch hot path -------------------------------------------
    def generate(self, prompts, gen: int) -> dict:
        """Prefill ``prompts`` (B, S) and decode ``gen`` tokens. Returns
        ``{"tokens": (B, gen) array, "prefill_s": float, "decode_s":
        float}`` — throughput is the caller's division to do."""
        import jax
        import jax.numpy as jnp

        from repro.models import encdec, steps

        B, S = prompts.shape
        s_max = S + gen
        with self._use_env(self.env):
            if self.cfg.is_encoder_decoder:
                frames = jax.random.normal(
                    self._key, (B, self.cfg.enc_seq, self.cfg.d_model),
                    jnp.bfloat16)
                memory = jax.jit(
                    lambda p, f: encdec.encode(p, f, self.cfg))(
                        self.params, frames)
                states = encdec.init_decode_state(
                    self.params, memory, self.cfg, B, s_max)
                tok = jnp.zeros((B, 1), jnp.int32)
                cache_len, t_pf = 0, 0.0
            else:
                t0 = time.perf_counter()
                tok, pf_states, _ = self._prefill(
                    self.params, {"tokens": prompts})
                jax.block_until_ready(tok)
                t_pf = time.perf_counter() - t0
                # move prefill KV into the fixed-capacity decode cache
                states = steps.decode_state(self.cfg, B, s_max)
                states = _install_prefill(states, pf_states, self.cfg, S)
                cache_len = S

            generated = [tok]
            t0 = time.perf_counter()
            for i in range(gen - 1):
                tok, states = self._decode(self.params, tok, states,
                                           jnp.int32(cache_len + i))
                generated.append(tok)
            jax.block_until_ready(tok)
            t_dec = time.perf_counter() - t0
        return {"tokens": jnp.concatenate(generated, axis=1),
                "prefill_s": t_pf, "decode_s": t_dec}

    # -- serving-tier adapter ---------------------------------------------
    def infer(self, payload=None) -> dict:
        """One inference request, as the workloads serving tier calls it
        (``POST /v2/workloads/{name}/invoke`` → attached engine). The
        payload is a dict of knobs: ``prompt_len`` (default 16),
        ``gen`` (default 8), ``batch`` (default 1); prompts are
        synthetic, like the launcher's."""
        import jax

        p = payload or {}
        B = int(p.get("batch", 1))
        S = int(p.get("prompt_len", 16))
        gen = max(2, int(p.get("gen", 8)))
        prompts = jax.random.randint(self._key, (B, S), 0,
                                     self.cfg.vocab_size)
        out = self.generate(prompts, gen)
        toks = out["tokens"]
        return {"arch": self.arch, "tokens": toks[0].tolist(),
                "batch": B, "prompt_len": S,
                "decode_ms_per_token":
                    out["decode_s"] / max(gen - 1, 1) * 1e3}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=8, help="batch size")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="1")
    ap.add_argument("--ctx-parallel", action="store_true",
                    help="shard the KV cache over the model axis (§Perf it.9)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    engine = ServeEngine(args.arch, tiny=args.tiny, mesh=args.mesh,
                         ctx_parallel=args.ctx_parallel, seed=args.seed)
    B, S = args.requests, args.prompt_len
    prompts = jax.random.randint(engine._key, (B, S), 0,
                                 engine.cfg.vocab_size)
    out = engine.generate(prompts, args.gen)
    toks, t_pf, t_dec = out["tokens"], out["prefill_s"], out["decode_s"]

    print(f"arch={engine.cfg.name} requests={B} prompt={S} "
          f"generated={toks.shape[1]}")
    if t_pf:
        print(f"prefill: {B * S / t_pf:,.0f} tok/s ({t_pf*1e3:.1f} ms)")
    print(f"decode:  {B * (args.gen - 1) / max(t_dec, 1e-9):,.0f} tok/s "
          f"({t_dec / max(args.gen - 1, 1) * 1e3:.2f} ms/token)")
    print(f"sample continuation (req 0): {toks[0, :12].tolist()}")


def _install_prefill(states, pf_states, cfg, prompt_len):
    """Write prefill-produced K/V into the decode cache at positions [0, S)."""
    import jax
    import jax.numpy as jnp
    from repro.nn.attention import KVCache

    def merge(slot, new):
        if isinstance(slot, jax.Array) and slot.ndim >= 3 and \
                new is not None and isinstance(new, jax.Array):
            return jax.lax.dynamic_update_slice_in_dim(
                slot, new.astype(slot.dtype), 0,
                axis=slot.ndim - 2)
        return slot

    # pf_states mirrors the decode-state structure (KVCache per attn layer,
    # recurrent state dicts pass through unchanged)
    def combine(s, p):
        if isinstance(s, KVCache) and isinstance(p, KVCache):
            return KVCache(k=merge(s.k, p.k), v=merge(s.v, p.v))
        return p if p is not None else s

    if isinstance(states, list):
        return [combine(s, p) for s, p in zip(states, pf_states)]
    # stacked scan layout: pytrees align leaf-wise
    return jax.tree.map(
        lambda s, p: merge(s, p) if hasattr(s, "ndim") else s,
        states, pf_states,
        is_leaf=lambda l: hasattr(l, "ndim"))


if __name__ == "__main__":
    main()

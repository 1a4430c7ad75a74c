"""Built-in loaders: rebuild predict functions from exported configs.

A loader is ``fn(config) -> (variables -> predict)`` where predict maps
{input_name: array} -> {output_name: array}.  Loader paths are recorded in
model.json at export time (serving/export.py).
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp


def classifier(config: Dict[str, Any]) -> Callable:
    """Image classifier over models/resnet.py or models/inception.py.

    config: {"family": "resnet50"|"inception_v3"|..., "num_classes": int}
    Signature: {"image": [b, h, w, 3] float32 or uint8} ->
               {"scores": [b, classes], "classes": [b, top_k]}

    Wire dtype is preserved on the host->device hop and converted on
    device: uint8 images (the reference's raw-image-bytes contract,
    components/k8s-model-server/inception-client/label.py) are scaled to
    [0, 1] inside the jitted forward — a quarter of the transfer bytes
    of a host-side float32 cast, which matters when the host link, not
    the MXU, bounds serving throughput.  float64/int64 (numpy's default
    from JSON lists) are narrowed host-side for the same reason.
    """
    family = config.get("family", "resnet50")
    num_classes = int(config.get("num_classes", 1000))
    top_k = min(int(config.get("top_k", 5)), num_classes)
    if family.startswith("resnet"):
        from kubeflow_tpu.models.resnet import ResNetConfig

        factory = ResNetConfig._FACTORIES.get(family)
        if factory is None:
            raise ValueError(f"unknown resnet family {family!r}")
        model = factory(
            num_classes=num_classes,
            num_filters=int(config.get("num_filters", 64)),
        )
    elif family == "inception_v3":
        from kubeflow_tpu.models.inception import InceptionV3

        model = InceptionV3(num_classes=num_classes)
    else:
        raise ValueError(f"unknown classifier family {family!r}")

    def make_predict(variables):
        @jax.jit
        def fwd(image):
            # dtype is trace-static: one compile per wire dtype.
            if image.dtype == jnp.uint8:
                image = image.astype(jnp.float32) / 255.0
            else:
                image = image.astype(jnp.float32)
            logits = model.apply(variables, image, train=False)
            probs = jax.nn.softmax(logits, axis=-1)
            top = jax.lax.top_k(probs, top_k)
            return probs, top

        def predict(inputs: Dict[str, Any]) -> Dict[str, Any]:
            import numpy as np

            image = inputs["image"]
            if isinstance(image, jax.Array):
                # Already device-resident (pipelined in-process callers):
                # never round-trip it through host numpy.
                pass
            else:
                image = np.asarray(image)
                if image.dtype == np.float64:
                    image = image.astype(np.float32)
                elif image.dtype.kind in "iu" and image.dtype != np.uint8:
                    # JSON integer pixels: ship as uint8 when they fit
                    # the 0..255 image range, else as float32.
                    if (image.size and 0 <= image.min()
                            and image.max() <= 255):
                        image = image.astype(np.uint8)
                    else:
                        image = image.astype(np.float32)
            if image.ndim == 3:
                image = image[None]
            probs, (top_p, top_i) = fwd(image)
            return {
                "scores": probs,
                "top_k_scores": top_p,
                "top_k_classes": top_i,
            }

        return predict

    return make_predict


def _model_config(overrides: Dict[str, Any]):
    """TransformerConfig from JSON-safe overrides (model.json carries
    dtype as a string, e.g. "float32"/"bfloat16")."""
    from kubeflow_tpu.models.transformer import TransformerConfig

    overrides = dict(overrides)
    if isinstance(overrides.get("dtype"), str):
        overrides["dtype"] = jnp.dtype(overrides["dtype"])
    return TransformerConfig(**overrides)


def lm_generate(config: Dict[str, Any]) -> Callable:
    """Autoregressive generation loader.

    config: {"model": TransformerConfig overrides,
             "max_new_tokens": int, "temperature": float,
             "top_k": int (0 = off), "top_p": float (1.0 = off),
             "quantize": "int8" (optional, weight-only),
             "kv_cache": "int8" (optional, quantized decode cache)}

    Sampling is deterministic per request (fixed seed): identical
    prompts return identical completions, the reproducibility contract
    a versioned model server wants.
    Signature: {"tokens": [b, t] int32} -> {"tokens": [b, t+new] int32}
    """
    from kubeflow_tpu.models.generate import DecodeConfig, generate

    cfg = _model_config(config.get("model", {}))
    kv_cache = config.get("kv_cache")
    if kv_cache not in (None, "int8"):
        raise ValueError(f"unknown kv_cache mode {kv_cache!r}")
    decode = DecodeConfig(
        max_new_tokens=int(config.get("max_new_tokens", 64)),
        temperature=float(config.get("temperature", 0.0)),
        top_k=int(config.get("top_k", 0)),
        top_p=float(config.get("top_p", 1.0)),
        eos_token=int(config.get("eos_token", -1)),
        kv_cache_dtype=kv_cache or "model",
    )
    quantize = config.get("quantize")
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown quantize mode {quantize!r}")
    if quantize and cfg.layer_types:
        raise ValueError(
            "quantize is not built for a model with layer_types (its "
            "experts' grouped products take plain arrays)")

    def make_predict(variables):
        # Stage weights into HBM ONCE at load.  They are an argument to
        # the jitted generate (not a closure constant), and jit
        # re-transfers host-numpy arguments on every call: the whole
        # model would cross the host link once per request.
        # Weight-only int8 quantization happens host-side BEFORE the
        # staging transfer (fewer bytes over the link, fewer HBM reads
        # per decoded token; ops/quantize.py).  Without it, matmul
        # weights are narrowed to the model compute dtype at staging:
        # checkpoints carry float32 masters, and serving float32 would
        # double every per-token weight read just to feed casts the
        # matmuls do anyway.  1D params (norm scales) stay float32 —
        # byte-free and precision-relevant.
        params = variables["params"]
        if quantize == "int8":
            from kubeflow_tpu.ops.quantize import quantize_params

            params = quantize_params(params)
        else:
            from kubeflow_tpu.ops.quantize import narrow_params

            params = narrow_params(params, cfg.dtype)
        params = jax.device_put(params)

        def predict(inputs: Dict[str, Any]) -> Dict[str, Any]:
            tokens = jnp.asarray(inputs["tokens"], jnp.int32)
            sd = inputs.get("seed")
            # Same sampling-seed contract as the DecodeEngine: a seeded
            # request falling back to this path (prompt too wide for
            # the engine, or engine disabled) must not silently sample
            # from the fixed default stream.  One seed per CALL — the
            # BucketedLMBatcher declines seeded requests so they arrive
            # here unbatched.
            rng = None
            if sd is not None:
                rng = jax.random.PRNGKey(
                    int(jnp.asarray(sd).reshape(-1)[0]))
            plen = inputs.get("prompt_len")
            if plen is not None:
                # Left-padded bucketed batch (BucketedLMBatcher): rows
                # decode at their real lengths; pad keys are masked.
                plen = jnp.asarray(plen, jnp.int32).reshape(-1)
                out, _ = generate(cfg, params, tokens, decode,
                                  rng=rng, prompt_len=plen)
            else:
                out, _ = generate(cfg, params, tokens, decode, rng=rng)
            req = inputs.get("max_new_tokens")
            if req is not None:
                # Per-request completion budget, same contract as the
                # DecodeEngine: a prompt that falls back to this path
                # (too wide for the engine's prefill width, or the
                # engine disabled) must not silently get the config's
                # full budget instead.  generate() still decodes the
                # full program; only the surplus is trimmed.  The
                # output array is rectangular, so a MULTI-row direct
                # call trims every row to the batch's LARGEST budget
                # (rows asking for less still get at least what they
                # asked); per-row budgets need per-row calls or the
                # engine/batcher paths.
                lim = int(jnp.max(jnp.asarray(req)))
                lim = max(1, min(lim, decode.max_new_tokens))
                out = out[:, : tokens.shape[1] + lim]
            return {"tokens": out}

        # Continuous-batching hook: the DecodeEngine (serving/engine.py)
        # needs the model itself — config, HBM-staged params, decode
        # settings — not a predict closure.  Exposing them here lets the
        # serving entrypoint build the engine around every hot-swapped
        # version exactly as it rebuilds batchers.
        predict.engine_spec = {"cfg": cfg, "params": params,
                               "decode": decode}
        return predict

    return make_predict


def lm(config: Dict[str, Any]) -> Callable:
    """Transformer LM loader: next-token logits for a token batch.

    config: TransformerConfig field overrides.
    Signature: {"tokens": [b, s] int32} -> {"logits": [b, s, vocab]}
    """
    from kubeflow_tpu.models.transformer import Transformer

    cfg = _model_config(config)
    model = Transformer(cfg)

    def make_predict(variables):
        from kubeflow_tpu.ops.quantize import narrow_params

        # Stage weights to HBM once, narrowed to the compute dtype —
        # the same treatment lm_generate got: raw orbax-restored numpy
        # leaves passed into jit are re-uploaded per call, and numpy
        # embedding tables cannot be fancy-indexed by a tracer at all
        # (the bf16 path crashed before any perf question arose).
        params = jax.device_put(
            narrow_params(variables["params"], cfg.dtype))

        @jax.jit
        def fwd(params, tokens):
            # Params are a jit ARGUMENT (not a closure constant —
            # closed-over arrays can be baked into the executable as a
            # second resident copy; lm_generate passes them the same
            # way).  Full-precision logits on the wire regardless of
            # the model's ce_dtype (a training-loss knob that changes
            # the forward's output dtype; irrelevant to serving).
            return model.apply(
                {"params": params}, tokens).astype(jnp.float32)

        def predict(inputs: Dict[str, Any]) -> Dict[str, Any]:
            tokens = jnp.asarray(inputs["tokens"], jnp.int32)
            return {"logits": fwd(params, tokens)}

        return predict

    return make_predict

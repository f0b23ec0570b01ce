"""GPT-mini decoder: causality, learnability, tensor-parallel sharding, and
the CLI path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.models.registry import build_gpt_mini
from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
from distributed_tensorflow_tpu.parallel import sync as sync_lib
from distributed_tensorflow_tpu.parallel.sharding import (
    replicate_state, shard_state)

SEQ = 32


def small_cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_position=64, dtype="float32")
    base.update(kw)
    return dataclasses.replace(gpt_lib.mini(), **base)


def build(cfg, batch=4):
    model = gpt_lib.GptLM(cfg)
    dummy = jnp.zeros((1, SEQ), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), dummy)["params"]
    tokens = gpt_lib.synthetic_lm_batch(0, batch, SEQ, cfg)["tokens"]
    return model, params, jnp.asarray(tokens)


@pytest.mark.smoke
def test_forward_shapes():
    cfg = small_cfg()
    model, params, tokens = build(cfg)
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (4, SEQ, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_causality_future_tokens_do_not_leak():
    cfg = small_cfg()
    model, params, tokens = build(cfg)
    logits = model.apply({"params": params}, tokens)
    # Perturb the LAST token; logits at all earlier positions must not move.
    perturbed = tokens.at[:, -1].set((tokens[:, -1] + 1) % cfg.vocab_size)
    logits_p = model.apply({"params": params}, perturbed)
    np.testing.assert_allclose(np.asarray(logits[:, :-1]),
                               np.asarray(logits_p[:, :-1]), atol=1e-6)
    # ...and the perturbed position itself must move (sanity).
    assert not np.allclose(np.asarray(logits[:, -1]),
                           np.asarray(logits_p[:, -1]))


def test_lm_loss_shapes_and_range():
    cfg = small_cfg()
    model, params, tokens = build(cfg)
    loss, acc = gpt_lib.lm_loss(model.apply({"params": params}, tokens),
                                tokens)
    assert np.isfinite(float(loss)) and float(loss) > 0
    assert 0.0 <= float(acc) <= 1.0


def test_gpt_trains_on_synthetic_stream():
    import optax

    mesh = mesh_lib.data_parallel_mesh()
    # Uncapped Adam: the registry caps --learning_rate at 1e-3; 3e-3 converges
    # in ~100 steps on the affine-bigram stream (measured: loss 6.0 -> 1.5,
    # next-token accuracy ~0.7).
    bundle = build_gpt_mini(1e-3, seq_len=SEQ, dtype="float32",
                            tx=optax.adam(3e-3))
    state = replicate_state(mesh, bundle.state)
    step = sync_lib.build_sync_train_step(mesh, bundle.loss_fn)
    sharding = mesh_lib.batch_sharding(mesh)
    split = bundle.load_datasets(None).train
    first_loss = final_loss = None
    for _ in range(100):
        batch = jax.tree.map(lambda a: jax.device_put(a, sharding),
                             split.next_batch(32))
        state, metrics = step(state, batch)
        # Block every step: an unbounded async-dispatch queue can starve one
        # of the 8 virtual CPU device threads past XLA's 40 s collective
        # rendezvous timeout on a loaded machine (hard process abort).
        final_loss = float(metrics["loss"])
        if first_loss is None:
            first_loss = final_loss
    assert final_loss < first_loss * 0.5, (first_loss, final_loss)
    acc = bundle.make_eval_fn()(state, bundle.load_datasets(None).test)
    assert acc > 0.4, acc


def test_gpt_tensor_parallel_sharding():
    mesh = mesh_lib.create_mesh(data=4, model=2)
    bundle = build_gpt_mini(1e-3, seq_len=SEQ, dtype="float32")
    state = shard_state(mesh, bundle.state, bundle.sharding_rules)
    qkv = state.params["layer0"]["qkv"]["kernel"]
    assert not qkv.sharding.is_fully_replicated
    step = sync_lib.build_sync_train_step(mesh, bundle.loss_fn, donate=False)
    batch = jax.tree.map(
        lambda a: jax.device_put(a, mesh_lib.batch_sharding(mesh)),
        bundle.load_datasets(None).train.next_batch(8))
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.global_step) == 2


def test_generate_shapes_and_determinism():
    cfg = small_cfg()
    model, params, tokens = build(cfg)
    prompt = tokens[:, :8]
    out = jax.jit(lambda p, pr: gpt_lib.generate(model, p, pr, 6))(
        params, prompt)
    assert out.shape == (4, 14)
    np.testing.assert_array_equal(np.asarray(out[:, :8]), np.asarray(prompt))
    # Greedy decoding is deterministic.
    out2 = gpt_lib.generate(model, params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # Sampling needs an rng and differs from greedy often enough to notice.
    with pytest.raises(ValueError, match="rng"):
        gpt_lib.generate(model, params, prompt, 6, temperature=1.0)
    sampled = gpt_lib.generate(model, params, prompt, 6, temperature=5.0,
                               rng=jax.random.PRNGKey(3))
    assert sampled.shape == out.shape


def test_sample_logits_filters():
    rng = jax.random.PRNGKey(0)
    # Fixed logits: token 3 dominant, then 1, then 0, then 2.
    logits = jnp.asarray([[1.0, 2.0, 0.0, 5.0]] * 64)
    # top_k=1 is argmax regardless of temperature.
    out = gpt_lib.sample_logits(logits, rng, temperature=10.0, top_k=1)
    assert np.all(np.asarray(out) == 3)
    # Tiny nucleus keeps only the dominant token.
    out = gpt_lib.sample_logits(logits, rng, temperature=10.0, top_p=1e-6)
    assert np.all(np.asarray(out) == 3)
    # top_k=2 at high temperature samples ONLY from {3, 1}.
    keys = jax.random.split(jax.random.PRNGKey(1), 20)
    draws = np.concatenate([
        np.asarray(gpt_lib.sample_logits(logits, k, temperature=50.0,
                                         top_k=2)) for k in keys])
    assert set(np.unique(draws)) <= {1, 3}
    assert len(set(np.unique(draws))) == 2  # high temp: both appear


def test_sampled_generation_cached_matches_full():
    """Both decode paths share the sampling helper and rng discipline, so
    sampled outputs (not just greedy) must agree token-for-token."""
    cfg = small_cfg()
    model, params, tokens = build(cfg)
    prompt = tokens[:, :8]
    kw = dict(temperature=1.0, top_k=8, top_p=0.9,
              rng=jax.random.PRNGKey(7))
    full = gpt_lib.generate(model, params, prompt, 8, **kw)
    cached = gpt_lib.generate_cached(model, params, prompt, 8, **kw)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


def test_generate_rejects_bad_top_p():
    cfg = small_cfg()
    model, params, tokens = build(cfg)
    with pytest.raises(ValueError, match="top_p"):
        gpt_lib.generate(model, params, tokens[:, :8], 4, temperature=1.0,
                         top_p=1.5, rng=jax.random.PRNGKey(0))


def test_cached_generation_matches_full_recompute():
    """KV-cached decode must produce exactly the greedy tokens of the O(S²)
    full-recompute path (same math, different schedule)."""
    cfg = small_cfg()
    model, params, tokens = build(cfg)
    prompt = tokens[:, :8]
    full = gpt_lib.generate(model, params, prompt, 10)
    cached = jax.jit(
        lambda p, pr: gpt_lib.generate_cached(model, p, pr, 10))(params, prompt)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


def test_ring_backend_model_still_decodes():
    """generate_cached on a ring-attention-trained model: prefill must fall
    back to plain attention (no mesh at decode) instead of raising."""
    import dataclasses

    cfg = dataclasses.replace(
        gpt_lib.mini(), vocab_size=32, hidden_size=16, num_layers=1,
        num_heads=2, intermediate_size=32, max_position=32,
        dtype="float32", attention_backend="ring")
    model = gpt_lib.GptLM(cfg)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    from distributed_tensorflow_tpu.ops.attention import attention_mesh
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    with attention_mesh(mesh_lib.create_mesh(data=4, seq=2)):
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    out = gpt_lib.generate_cached(model, params, prompt, 4)
    assert out.shape == (1, 8)


def test_trained_model_generates_the_stream_rule():
    """After training on the affine-bigram stream, greedy continuation should
    reproduce the generating rule x[t+1] = (3 x[t] + t) % vocab."""
    import optax

    mesh = mesh_lib.data_parallel_mesh()
    # Constant 3e-3 learns the rule but free-running generation is
    # unstable from run to run (measured 0.41-0.84 rule-following across
    # nearby step counts); cosine-decaying to zero converges the policy
    # cleanly (measured 0.94 stable from step 160 on).
    bundle = build_gpt_mini(1e-3, seq_len=SEQ, dtype="float32",
                            tx=optax.adam(
                                optax.cosine_decay_schedule(3e-3, 240)))
    state = replicate_state(mesh, bundle.state)
    step = sync_lib.build_sync_train_step(mesh, bundle.loss_fn)
    sharding = mesh_lib.batch_sharding(mesh)
    split = bundle.load_datasets(None).train
    for _ in range(240):
        batch = jax.tree.map(lambda a: jax.device_put(a, sharding),
                             split.next_batch(32))
        state, metrics = step(state, batch)
        float(metrics["loss"])  # keep the dispatch queue shallow (see above)

    from distributed_tensorflow_tpu.models.gpt import GptLM, mini
    import dataclasses as _dc
    cfg = _dc.replace(mini(), dtype="float32")
    model = GptLM(cfg)
    clean = gpt_lib.synthetic_lm_batch(123, 4, SEQ, cfg)["tokens"]
    prompt = jnp.asarray(clean[:, :16])
    gen_len = 8
    params = jax.device_get(state.params)
    out = np.asarray(gpt_lib.generate(model, params, prompt, gen_len))
    # Expected continuation by the rule, seeded from the model's own output
    # (teacher-forcing-free: one wrong token may cascade, so seed each check
    # from the previous *generated* token).
    correct = 0
    for b in range(out.shape[0]):
        for t in range(16, 16 + gen_len):
            expect = (3 * out[b, t - 1] + (t - 1)) % cfg.vocab_size
            correct += int(out[b, t] == expect)
    frac = correct / (out.shape[0] * gen_len)
    assert frac > 0.5, (frac, out[:, 12:])


def test_generate_mode_cli(tmp_path, monkeypatch, capsys):
    """--mode=generate restores the latest checkpoint and decodes."""
    from distributed_tensorflow_tpu.train import FLAGS, main
    from helpers import patch_standalone_server
    patch_standalone_server(monkeypatch)

    common = [
        "--job_name=worker", "--task_index=0", "--data_dir=/nonexistent",
        "--worker_hosts=localhost:0", "--ps_hosts=localhost:0",
        "--model=gpt_mini", "--bert_seq_len=32", "--batch_size=8",
        f"--logdir={tmp_path}/logdir",
    ]
    FLAGS.parse(common + ["--sync_replicas=true", "--train_steps=4",
                          "--save_interval_steps=2", "--log_every=2"])
    main([])
    capsys.readouterr()

    FLAGS.parse(common + ["--mode=generate", "--gen_tokens=6",
                          "--gen_temperature=0.8", "--gen_top_k=10"])
    toks = main([])
    out = capsys.readouterr().out
    assert "Restored global step:" in out
    assert "Generated tokens:" in out
    # Step restored from the training run's checkpoint, not random init.
    step_line = [l for l in out.splitlines()
                 if l.startswith("Restored global step:")][0]
    assert int(step_line.split(":")[1]) >= 4
    gen_line = [l for l in out.splitlines()
                if l.startswith("Generated tokens:")][0]
    assert len(gen_line.split(":")[1].split()) == 6
    assert toks is not None


def test_generate_mode_custom_prompt(tmp_path, monkeypatch, capsys):
    from distributed_tensorflow_tpu.train import FLAGS, main
    FLAGS.parse([
        "--job_name=worker", "--task_index=0", "--mode=generate",
        "--model=gpt_mini", "--gen_prompt=5,10,15", "--gen_tokens=4",
        f"--logdir={tmp_path}/empty",
    ])
    main([])
    out = capsys.readouterr().out
    assert "Prompt tokens:    5 10 15" in out
    assert len([l for l in out.splitlines()
                if l.startswith("Generated tokens:")][0].split(":")[1]
               .split()) == 4

    FLAGS.parse([
        "--job_name=worker", "--task_index=0", "--mode=generate",
        "--model=gpt_mini", "--gen_prompt=5,999", f"--logdir={tmp_path}/e2",
    ])
    with pytest.raises(ValueError, match="outside vocab"):
        main([])


def test_generate_mode_rejects_non_gpt(tmp_path, monkeypatch):
    from distributed_tensorflow_tpu.train import FLAGS, main
    FLAGS.parse([
        "--job_name=worker", "--task_index=0", "--mode=generate",
        "--model=mnist_mlp", f"--logdir={tmp_path}/logdir",
    ])
    with pytest.raises(ValueError, match="autoregressive"):
        main([])


def test_gpt_cli_e2e(tmp_path, monkeypatch):
    from distributed_tensorflow_tpu.train import FLAGS, main
    from helpers import patch_standalone_server
    patch_standalone_server(monkeypatch)

    FLAGS.parse([
        "--job_name=worker", "--task_index=0", "--data_dir=/nonexistent",
        "--worker_hosts=localhost:0", "--ps_hosts=localhost:0",
        "--model=gpt_mini", "--bert_seq_len=32", "--sync_replicas=true",
        "--train_steps=4", "--batch_size=8", "--log_every=2",
        f"--logdir={tmp_path}/logdir",
    ])
    result = main([])
    assert result.final_global_step >= 4
    assert result.test_accuracy is not None


def test_builder_rejects_tiny_bpe_vocab():
    """Direct API callers (not just the CLI) must hit the >=257 invariant:
    a smaller table would under-cover the byte-fallback id range."""
    with pytest.raises(ValueError, match="257"):
        build_gpt_mini(0.1, tokenizer="bpe", bpe_vocab=100)


# --------------------------------------- the pallas backend on a data mesh

def _pallas_step_inputs(mesh, backend="pallas", dropout_rate=0.0, rows=8):
    import optax
    bundle = build_gpt_mini(1e-3, seq_len=SEQ, dtype="float32",
                            attention_backend=backend, tx=optax.sgd(0.5),
                            dropout_rate=dropout_rate)
    batch = jax.tree.map(
        lambda a: jax.device_put(a, mesh_lib.batch_sharding(mesh)),
        bundle.load_datasets(None).train.next_batch(rows))
    return bundle, batch


def _assert_same_step(got, want, tol=1e-5):
    (state_a, metrics_a), (state_b, metrics_b) = got, want
    np.testing.assert_allclose(float(metrics_a["loss"]),
                               float(metrics_b["loss"]), rtol=tol)
    # Plain SGD: the parameters' change IS the gradient, times the rate.
    for a, b in zip(jax.tree.leaves(state_a.params),
                    jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol)


def test_pallas_step_on_a_data_mesh_maps_its_kernels_over_the_batch():
    from helpers import kernel_placement
    mesh = mesh_lib.create_mesh(data=4, devices=jax.devices()[:4])
    bundle, batch = _pallas_step_inputs(mesh)
    step = sync_lib.build_sync_train_step(mesh, bundle.loss_fn, donate=False)
    state = replicate_state(mesh, bundle.state)
    # Per layer one kernel forward and one backward, all inside a shard_map.
    layers = gpt_lib.mini().num_layers
    assert kernel_placement(step, state, batch) == (2 * layers, 0)
    mapped = step(state, batch)

    one = mesh_lib.create_mesh(data=1, devices=jax.devices()[:1])
    bundle1, batch1 = _pallas_step_inputs(one)
    step1 = sync_lib.build_sync_train_step(one, bundle1.loss_fn, donate=False)
    assert kernel_placement(step1, bundle1.state, batch1) == (0, 2 * layers)
    _assert_same_step(mapped,
                      step1(replicate_state(one, bundle1.state), batch1))

    dense, _ = _pallas_step_inputs(mesh, backend="xla")
    _assert_same_step(mapped, sync_lib.build_sync_train_step(
        mesh, dense.loss_fn, donate=False)(state, batch))


def _scan_last(body, state, batches):
    state, stacked = jax.lax.scan(body, state, batches, length=2)
    return state, jax.tree.map(lambda m: m[-1], stacked)


@pytest.mark.parametrize("case", ["replicated", "fsdp", "needs_rng",
                                  "scanned", "model_axis"])
def test_pallas_step_on_a_mesh_computes_what_the_unmapped_step_did(case):
    """The step as it was built before the builders made their mesh ambient
    (a bare ``jax.jit`` of the same body: GSPMD partitions the interpreted
    kernel) gives the same loss and the same update: FSDP-placed state, a
    dropout key drawn once for the global batch and the scanned builder
    included; a mesh with a ``model`` axis is not mapped at all."""
    from helpers import kernel_placement
    from distributed_tensorflow_tpu.parallel.sharding import fsdp_state
    axes = dict(data=2, model=2) if case == "model_axis" else dict(data=4)
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:4], **axes)
    needs_rng = case == "needs_rng"
    bundle, batch = _pallas_step_inputs(
        mesh, dropout_rate=0.1 if needs_rng else 0.0)
    state = (fsdp_state(mesh, bundle.state, min_size=1024) if case == "fsdp"
             else replicate_state(mesh, bundle.state))
    body = sync_lib._grad_and_update(bundle.loss_fn, needs_rng)
    if case == "scanned":
        batch = jax.tree.map(
            lambda a: jax.device_put(jnp.stack([a, a[::-1]]),
                                     mesh_lib.stacked_batch_sharding(mesh)),
            batch)
        step = sync_lib.build_scanned_sync_train_step(
            mesh, bundle.loss_fn, num_steps=2, donate=False)
        before = jax.jit(lambda s, b: _scan_last(body, s, b))
    else:
        step = sync_lib.build_sync_train_step(
            mesh, bundle.loss_fn, needs_rng=needs_rng, donate=False)
        before = jax.jit(body)
    calls = 2 * gpt_lib.mini().num_layers
    assert kernel_placement(step, state, batch) == (
        (0, calls) if case == "model_axis" else (calls, 0))
    assert kernel_placement(before, state, batch) == (0, calls)
    _assert_same_step(step(state, batch), before(state, batch))

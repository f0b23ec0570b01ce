"""The BASELINE.json config ladder, driven through the real CLI main():
MLP sync (covered in test_train_e2e.py) → LeNet-5 async → ResNet-20 sync →
BERT-tiny sync.  Small step counts: these pin the *wiring* (model registry →
step builder → loop → eval) per rung; convergence is covered by the library
tests in test_models.py."""

import pytest

from distributed_tensorflow_tpu.train import FLAGS, main


def run_main(tmp_path, extra_flags):
    argv = [
        "--job_name=worker", "--task_index=0",
        "--data_dir=/nonexistent",
        "--worker_hosts=localhost:0", "--ps_hosts=localhost:0",
        "--batch_size=16", "--learning_rate=0.05", "--log_every=2",
        f"--logdir={tmp_path}/logdir",
    ] + extra_flags
    FLAGS.parse(argv)
    return main([])


@pytest.fixture(autouse=True)
def no_coord(monkeypatch):
    from helpers import patch_standalone_server
    patch_standalone_server(monkeypatch)


def test_ladder_lenet5_async(tmp_path):
    # Rung 3: LeNet-5, async replicas (the reference's default mode).
    result = run_main(tmp_path, ["--model=lenet5", "--sync_replicas=false",
                                 "--async_sync_period=2",
                                 "--train_steps=48"])  # 8 replicas x 6 local
    assert result.final_global_step >= 48
    assert result.test_accuracy is not None


def test_ladder_resnet20_sync(tmp_path):
    # Rung 4: ResNet-20 (BatchNorm => stateful sync step, cross-replica
    # batch statistics via GSPMD).
    result = run_main(tmp_path, ["--model=resnet20", "--sync_replicas=true",
                                 "--train_steps=4", "--batch_size=16"])
    assert result.final_global_step >= 4
    assert result.last_loss is not None
    assert result.test_accuracy is not None


def test_sequence_parallel_ring_bert(tmp_path):
    # Long-context path through the CLI: 'seq' mesh axis + ring attention.
    result = run_main(tmp_path, ["--model=bert_tiny", "--sync_replicas=true",
                                 "--sequence_parallel=2",
                                 "--attention_backend=ring",
                                 "--train_steps=3", "--bert_seq_len=32",
                                 "--batch_size=8"])
    assert result.final_global_step >= 3
    assert result.test_accuracy is not None


def test_sequence_parallel_ring_gpt(tmp_path):
    # Causal ring attention through the CLI (decoder + seq axis).
    result = run_main(tmp_path, ["--model=gpt_mini", "--sync_replicas=true",
                                 "--sequence_parallel=2",
                                 "--attention_backend=ring",
                                 "--train_steps=3", "--bert_seq_len=32",
                                 "--batch_size=8"])
    assert result.final_global_step >= 3
    assert result.test_accuracy is not None


def test_ladder_bert_tiny_sync(tmp_path):
    # Rung 5: BERT-tiny MLM sync (transformer; Adam; bf16 activations).
    result = run_main(tmp_path, ["--model=bert_tiny", "--sync_replicas=true",
                                 "--train_steps=4", "--bert_seq_len=32",
                                 "--batch_size=8"])
    assert result.final_global_step >= 4
    assert result.test_accuracy is not None


def test_bert_tiny_fused_layer_norm(tmp_path):
    # --fused_layer_norm: pallas LN kernel through the CLI (N5 hot-op path).
    result = run_main(tmp_path, ["--model=bert_tiny", "--sync_replicas=true",
                                 "--fused_layer_norm=true",
                                 "--train_steps=3", "--bert_seq_len=32",
                                 "--batch_size=8"])
    assert result.final_global_step >= 3
    assert result.test_accuracy is not None


def test_dcn_data_parallel_flag(tmp_path):
    # Hybrid multi-slice layout through the CLI: 2 "slices" x 4 devices on
    # the virtual mesh; the data axis's outer factor crosses slice groups.
    result = run_main(tmp_path, ["--sync_replicas=true",
                                 "--dcn_data_parallel=2",
                                 "--train_steps=4"])
    assert result.final_global_step >= 4
    assert result.test_accuracy is not None

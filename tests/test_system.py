"""End-to-end behaviour tests: drivers, enrichment, pipeline parallelism,
HLO cost model."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.corpus import Corpus, Table
from repro.core.index import MateIndex
from repro.data import synthetic
from repro.data.enrichment import enrich, tokenize_records


def test_train_driver_end_to_end(tmp_path):
    from repro.launch.train import main

    losses = main(
        [
            "--arch", "qwen1.5-0.5b", "--smoke", "--steps", "8",
            "--seq-len", "32", "--global-batch", "4",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "4", "--lr", "5e-3",
        ]
    )
    assert losses[-1] < losses[0]
    # resume path: second invocation starts from the checkpoint
    losses2 = main(
        [
            "--arch", "qwen1.5-0.5b", "--smoke", "--steps", "10",
            "--seq-len", "32", "--global-batch", "4",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "4", "--lr", "5e-3",
        ]
    )
    assert len(losses2) == 2  # resumed at step 8 of 10


def test_serve_driver_end_to_end():
    from repro.launch.serve import main

    done = main(
        ["--arch", "qwen1.5-0.5b", "--smoke", "--batch", "2",
         "--max-seq", "48", "--max-new", "4", "--n-requests", "3"]
    )
    assert all(len(r.out) == 4 for r in done)


def test_discovery_driver_end_to_end(capsys):
    from repro.launch.discovery import main

    main(["--n-tables", "80", "--queries", "2", "--rows", "10"])
    out = capsys.readouterr().out
    assert "precision" in out and "distributed filter" in out


def test_discovery_driver_sharded_build_subprocess():
    """--build-mesh N: the driver forces N virtual devices, builds the
    session over the mesh (shard_map hash pass + host merge) and the
    engines stay bit-identical — subprocess because the device count must
    be set before jax initialises."""
    res = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.discovery",
            "--build-mesh", "4", "--n-tables", "60", "--queries", "1",
            "--rows", "8",
        ],
        capture_output=True, text=True, timeout=600,
        cwd=__file__.rsplit("/", 2)[0],
        env={
            **__import__("os").environ, "PYTHONPATH": "src",
            "JAX_ENABLE_COMPILATION_CACHE": "false",
        },
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "build stats: shards=4 mesh={'data': 4}" in res.stdout, res.stdout
    # default rank is 'quality' (ISSUE 9): the driver compares engine SETS
    assert "engines_set_identical=True" in res.stdout


def test_discovery_driver_rank_flags_subprocess():
    """--rank/--no-profile-gate: quality rank reports the gate counters and
    count rank restores the exact engines_bit_identical comparison."""
    import os

    env = {
        **os.environ, "PYTHONPATH": "src",
        "JAX_ENABLE_COMPILATION_CACHE": "false",
    }
    cwd = __file__.rsplit("/", 2)[0]
    res = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.discovery",
            "--n-tables", "80", "--queries", "2", "--rows", "8",
            "--rank", "quality",
        ],
        capture_output=True, text=True, timeout=600, cwd=cwd, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "engines_set_identical=True" in res.stdout, res.stdout
    assert "profile gate (on, rank=quality)" in res.stdout, res.stdout
    assert "ranking_launches=" in res.stdout

    res = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.discovery",
            "--n-tables", "80", "--queries", "2", "--rows", "8",
            "--rank", "count", "--no-profile-gate",
        ],
        capture_output=True, text=True, timeout=600, cwd=cwd, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "engines_bit_identical=True" in res.stdout, res.stdout
    assert "profile gate (off, rank=count)" in res.stdout, res.stdout


def test_enrichment_operator():
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=50, seed=4))
    base_cells = [["k%da" % i, "k%db" % i, "payload"] for i in range(10)]
    # inject joinable rows with extra feature columns into a corpus table
    feature_rows = [["k%da" % i, "k%db" % i, "feat%d" % i, "extra"] for i in range(8)]
    tid = len(corpus.tables)
    corpus.tables.append(Table(tid, feature_rows))
    corpus = Corpus(corpus.tables)
    index = MateIndex(corpus)
    base = Table(-1, base_cells)
    enriched, prov = enrich(index, base, [0, 1], k=3)
    assert enriched.n_cols > base.n_cols
    assert any(p["table_id"] == tid and p["hit_rows"] == 8 for p in prov)
    toks = tokenize_records(enriched, vocab_size=1000, seq_len=32)
    assert toks.shape == (10, 32)
    assert toks.max() < 1000


def test_pipeline_parallel_subprocess():
    """GPipe loss == non-pipelined loss (8 fake devices, 2 stages)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import dataclasses, jax, jax.numpy as jnp
        from repro import configs
        from repro.launch import mesh as meshlib
        from repro.models import transformer, params as P_
        from repro.train import pipeline as PP
        from repro.train.step import chunked_ce

        cfg = configs.reduce_config(configs.get_config("qwen1.5-0.5b"))
        cfg = dataclasses.replace(cfg, n_layers=4)
        specs = transformer.model_specs(cfg)
        params = P_.materialize(specs, jax.random.PRNGKey(0))
        B, S = 16, 16
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        labels = jnp.concatenate([tokens[:, 1:], -jnp.ones((B, 1), jnp.int32)], 1)
        hidden, _ = transformer.forward_hidden(params, cfg, tokens, remat=False)
        ref = chunked_ce(hidden, params["embed"].T.astype(hidden.dtype), labels, 0, 0.0)
        mesh = meshlib.make_mesh((2, 4), ("pod", "data"))
        staged = PP.stage_view(params, 2)
        fn = PP.pipeline_loss_fn(cfg, mesh, 2, staged, batch_axes=("data",))
        with mesh:
            out = jax.jit(fn)(staged, tokens, labels)
        diff = abs(float(out) - float(ref))
        assert diff < 1e-3, diff
        print("PP_OK", diff)
        """
    )
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=__file__.rsplit("/", 2)[0], timeout=600,
    )
    assert "PP_OK" in res.stdout, res.stderr[-2000:]


def test_hlo_cost_model_counts_loop_trips():
    """Corrected flops must scale with scan trip count (XLA's raw
    cost_analysis does not)."""
    from repro.launch import hlo_cost

    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jnp.ones((32, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    got = hlo_cost.analyze(compiled.as_text())["flops"]
    want = 7 * 2 * 32 * 64 * 64
    assert abs(got - want) / want < 0.05, (got, want)

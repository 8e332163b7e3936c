"""End-to-end system test: train -> checkpoint -> restore -> serve.

The full lifecycle a production framework must support, on a reduced
config: the Trainer fits a synthetic bigram LM, checkpoints; a fresh
process-equivalent restore feeds the serving engine; generated text must
reflect the learned bigram structure (better-than-chance next-token hits).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointStore
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data import SyntheticLM
from repro.serve import Request, ServeEngine
from repro.train import Trainer


def test_train_checkpoint_serve_lifecycle(tmp_path):
    cfg = registry.reduced_config("qwen1.5-0.5b").replace(vocab=64)
    ck = str(tmp_path / "ck")
    tcfg = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=200,
                       checkpoint_every=40, checkpoint_dir=ck, remat=True)
    data = SyntheticLM(vocab=64, seq_len=32, global_batch=16, seed=0)
    trainer = Trainer(cfg, tcfg, global_batch=16, seq_len=32, data=data,
                      log=lambda *_: None)
    m0 = trainer.run(10)
    m1 = trainer.run(110)
    assert m1["loss"] < m0["loss"] - 0.5, (m0["loss"], m1["loss"])
    trainer.save(trainer.start_step)

    # fresh restore (as a new process would)
    store = CheckpointStore(ck)
    state_like = jax.eval_shape(lambda: trainer.state)
    restored, step, _ = store.restore(state_like)
    assert step == trainer.start_step
    params = restored.params

    # serve with the trained weights; outputs should follow the bigram LM
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                      prefill_buckets=(8,))
    tbl = np.asarray(jax.nn.softmax(data._tbl, axis=-1))
    prompts = [[int(t) for t in data.batch(999)[0][i, :6]]
               for i in range(4)]
    outs = eng.run([Request(rid=i, prompt=p, max_new=12)
                    for i, p in enumerate(prompts)])
    hits = total = 0
    for i, p in enumerate(prompts):
        seq = p + outs[i]
        for a, b in zip(seq[:-1], seq[1:]):
            # learned transitions should land in the bigram's top-8 set
            hits += int(b in np.argsort(tbl[a])[-8:])
            total += 1
    rate = hits / total
    assert rate > 0.35, f"served tokens ignore learned bigram: {rate:.2f}"


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, stands untouched; otherwise
    the cache goes to the fixed <checkout>/.jax_cache."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").is_file()
    assert calls == [("jax_compilation_cache_dir", path)]

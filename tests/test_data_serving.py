"""Data plane (loader determinism/resume) + scoring microservice + batcher."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.core import RecordBatch
from repro.core.flight import FlightClient, FlightDescriptor, InMemoryFlightServer
from repro.data import FlightDataLoader, LoaderState, pack_documents, synthesize_corpus
from repro.distributed.sharding import single_device_ctx
from repro.models.lm import LM
from repro.serving import Batcher, BatcherConfig, LMScoringService, score_tokens

# every family the service can feed: token prompts, no vision or audio frontend
SERVABLE = [a for a in ARCH_IDS if get_smoke_config(a).frontend is None]
INTERNLM2_MATMUL = {"embedding", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}


def off_bf16_grid(params, key):
    """Moves each leaf ``init`` fills with one constant (norm weights and
    biases, ``D_skip``, ``A_log``, ``b_dt``...) to c + 1e-3·normal, values bf16
    cannot hold: such a leaf held in bf16 then changes the answers."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        x + 1e-3 * jax.random.normal(k, x.shape, x.dtype) if bool(jnp.all(x == x.ravel()[0]))
        else x for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def corpus_server():
    srv = InMemoryFlightServer(batches_per_endpoint=1)
    srv.add_dataset("corpus", synthesize_corpus(2000, 512, mean_len=150, seed=7,
                                                batch_docs=250))
    return srv


class TestDataset:
    def test_corpus_is_columnar_and_reproducible(self):
        a = synthesize_corpus(100, 64, seed=3)
        b = synthesize_corpus(100, 64, seed=3)
        assert a[0] == b[0]

    def test_pack_documents_shapes_and_continuity(self):
        shard = synthesize_corpus(50, 64, seed=1)[0]
        rows = pack_documents(shard, seq_len=32)
        assert rows.shape[1] == 33
        flat = shard.column("tokens").children[0].to_numpy()
        assert np.array_equal(rows.reshape(-1), flat[: rows.size])


class TestLoader:
    def test_shapes_and_label_shift(self, corpus_server):
        loader = FlightDataLoader(FlightClient(corpus_server), "corpus",
                                  batch_size=4, seq_len=64, streams=2)
        batch, state = next(loader)
        loader.close()
        assert batch["tokens"].shape == (4, 64)
        assert np.array_equal(batch["tokens"][:, 1:], batch["labels"][:, :-1])

    def test_determinism_across_instances(self, corpus_server):
        def first_batch():
            l = FlightDataLoader(FlightClient(corpus_server), "corpus",
                                 batch_size=4, seq_len=64, streams=2, seed=5)
            b, _ = next(l)
            l.close()
            return b["tokens"]
        assert np.array_equal(first_batch(), first_batch())

    def test_hosts_get_disjoint_shards(self, corpus_server):
        l0 = FlightDataLoader(FlightClient(corpus_server), "corpus", batch_size=2,
                              seq_len=32, host_id=0, n_hosts=2)
        l1 = FlightDataLoader(FlightClient(corpus_server), "corpus", batch_size=2,
                              seq_len=32, host_id=1, n_hosts=2)
        s0, s1 = set(l0._host_shards(0)), set(l1._host_shards(0))
        l0.close(); l1.close()
        assert not (s0 & s1) and len(s0 | s1) == l0.n_shards


class TestScoring:
    def test_exchange_scoring_roundtrip(self):
        cfg = get_smoke_config("internlm2_1_8b")
        model = LM(cfg, single_device_ctx())
        params, _ = model.init(jax.random.key(0))
        svc = LMScoringService(model, params, max_seq=32).serve_tcp()
        try:
            c = FlightClient(f"tcp://127.0.0.1:{svc.port}")
            req = RecordBatch.from_pydict({"tokens": [[1, 2, 3], [4, 5]]})
            ex = c.do_exchange_stream(FlightDescriptor.for_path("score"), req.schema)
            ex.feed([req])
            (out,) = list(ex)
            ex.close()
            assert out.schema.names == ["next_token", "logprob"]
            assert out.num_rows == 2
            assert all(0 <= t < cfg.vocab for t in out.column("next_token").to_pylist())
        finally:
            svc.shutdown()

    def test_ragged_batch_matches_unpadded_prefill(self):
        """Each row is answered from its own last real token: a ragged batch
        served over TCP equals a prefill of each prompt alone, unpadded."""
        cfg = get_smoke_config("internlm2_1_8b")
        model = LM(cfg, single_device_ctx())
        params, _ = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        rows = [rng.integers(1, cfg.vocab, n).tolist() for n in (3, 17, 32, 9, 1)]
        svc = LMScoringService(model, params, max_seq=32).serve_tcp()
        try:
            c = FlightClient(f"tcp://127.0.0.1:{svc.port}")
            req = RecordBatch.from_pydict({"tokens": rows})
            ex = c.do_exchange_stream(FlightDescriptor.for_path("score"), req.schema)
            ex.feed([req])
            (out,) = list(ex)
            ex.close()
        finally:
            svc.shutdown()
        for i, r in enumerate(rows):
            lg, _ = model.prefill(params, {"tokens": np.asarray([r], np.int32)})
            lp = jax.nn.log_softmax(np.asarray(lg[0], np.float32))
            assert out.column("next_token").to_pylist()[i] == int(np.argmax(lp))
            assert abs(out.column("logprob").to_pylist()[i] - float(np.max(lp))) <= 1e-5

    @pytest.mark.parametrize("arch", SERVABLE)
    def test_held_weights_score_bit_for_bit_as_float32(self, arch):
        """Matmul weights held in bf16 give exactly the answers of the float32
        tree the program would cast on every call."""
        cfg = get_smoke_config(arch)
        model = LM(cfg, single_device_ctx())
        params, _ = model.init(jax.random.key(0))
        params = off_bf16_grid(params, jax.random.key(1))
        svc = LMScoringService(model, params, max_seq=32)
        rng = np.random.default_rng(0)
        toks = rng.integers(1, cfg.vocab, (3, 32)).astype(np.int32)
        lens = np.asarray([32, 5, 17], np.int32)
        held = score_tokens(model, svc.params, toks, lens)
        f32 = score_tokens(model, params, toks, lens)
        for a, b in zip(held, f32):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert any(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(svc.params))

    def test_internlm2_holds_exactly_its_matmul_weights_in_bf16(self):
        model = LM(get_smoke_config("internlm2_1_8b"), single_device_ctx())
        params, _ = model.init(jax.random.key(0))
        held = {"/".join(k.key for k in path): x for path, x in
                jax.tree_util.tree_flatten_with_path(
                    LMScoringService(model, params, max_seq=32).params)[0]}
        bf16 = [k for k, x in held.items() if x.dtype == jnp.bfloat16]
        assert sorted(k.rsplit("/", 1)[-1] for k in bf16) == sorted(INTERNLM2_MATMUL)
        norms = [x for k, x in held.items() if "norm" in k]
        assert norms and all(x.dtype == jnp.float32 for x in norms)

    def test_weight_bytes_are_set_at_load(self):
        """``weight_bytes`` and ``weight_bytes_compute`` count what the service
        holds, reach ``server-metrics``, and no call moves them."""
        from repro.core.flight import Action, batch_to_rows, decode_telemetry_batch

        model = LM(get_smoke_config("internlm2_1_8b"), single_device_ctx())
        params, _ = model.init(jax.random.key(0))
        svc = LMScoringService(model, params, max_seq=32).serve_tcp()
        try:
            c = FlightClient(f"tcp://127.0.0.1:{svc.port}")

            def scrape() -> dict:
                rows = batch_to_rows(decode_telemetry_batch(
                    c.do_action(Action("server-metrics", b""))[0].body))
                return {r["name"]: r["count"] for r in rows if r["scope"] == "serve"}

            before = scrape()
            req = RecordBatch.from_pydict({"tokens": [[1, 2, 3]]})
            ex = c.do_exchange_stream(FlightDescriptor.for_path("score"), req.schema)
            ex.feed([req])
            assert len(list(ex)) == 1
            ex.close()
            after = scrape()
        finally:
            svc.shutdown()
        held = jax.tree.leaves(svc.params)
        total = sum(x.size * x.dtype.itemsize for x in held)
        compute = sum(x.size * 2 for x in held if x.dtype == jnp.bfloat16)
        counters = svc.serve_counters()
        assert (counters["weight_bytes"], counters["weight_bytes_compute"]) == (total, compute)
        assert total < sum(x.size * 4 for x in held)
        assert compute / total >= 0.99
        for k in ("weight_bytes", "weight_bytes_compute"):
            assert before[k] == after[k] == counters[k]

    def test_request_counter_survives_concurrent_workers(self):
        """Handlers of many connections bump the serve counters at once."""
        import sys

        from repro.serving import ScoringService

        svc = ScoringService(lambda b: b)
        batch = RecordBatch.from_pydict({"x": [1]})
        desc = FlightDescriptor.for_path("score")

        def worker():
            for _ in range(500):
                svc.do_exchange_impl(desc, batch.schema, batch)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=worker) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert svc.serve_counters() == {"requests": 16 * 500}

    def test_batcher_coalesces(self):
        calls = []

        def model_fn(toks, lens):
            calls.append(toks.shape[0])
            return toks.sum(axis=1)

        b = Batcher(BatcherConfig(max_batch=4, max_wait_s=0.1, pad_to=8), model_fn)
        results = {}

        def worker(i):
            results[i] = b.score(np.full(i + 1, i, np.int32))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(calls) == 1 and calls[0] == 4  # one coalesced model call
        for i in range(4):
            assert results[i] == i * (i + 1)


class TestGeneration:
    def test_greedy_generation_shapes_and_determinism(self):
        from repro.serving.generate import generate
        cfg = get_smoke_config("internlm2_1_8b")
        model = LM(cfg, single_device_ctx())
        params, _ = model.init(jax.random.key(0))
        prompts = np.random.default_rng(0).integers(1, cfg.vocab, (2, 6)).astype(np.int32)
        import jax.numpy as jnp
        out1 = generate(model, params, jnp.asarray(prompts), max_new_tokens=8)
        out2 = generate(model, params, jnp.asarray(prompts), max_new_tokens=8)
        assert out1.shape == (2, 8)
        assert np.array_equal(np.asarray(out1), np.asarray(out2))
        assert (np.asarray(out1) >= 0).all() and (np.asarray(out1) < cfg.vocab).all()

    def test_generation_recurrent_arch(self):
        from repro.serving.generate import generate
        cfg = get_smoke_config("xlstm_350m")
        model = LM(cfg, single_device_ctx())
        params, _ = model.init(jax.random.key(1))
        import jax.numpy as jnp
        prompts = np.random.default_rng(1).integers(1, cfg.vocab, (1, 4)).astype(np.int32)
        out = generate(model, params, jnp.asarray(prompts), max_new_tokens=5)
        assert out.shape == (1, 5)


class TestLoaderResume:
    def test_resume_from_state_skips_consumed_shards(self, corpus_server):
        """Checkpoint/restore of the loader ticket: a loader resumed from a
        mid-epoch state must not re-serve the shards before its cursor."""
        l0 = FlightDataLoader(FlightClient(corpus_server), "corpus",
                              batch_size=4, seq_len=64, streams=1, seed=11)
        b0, st = next(l0)
        l0.close()
        assert st.cursor > 0
        l1 = FlightDataLoader(FlightClient(corpus_server), "corpus",
                              batch_size=4, seq_len=64, streams=1, seed=11,
                              state=LoaderState(st.epoch, st.cursor))
        b1, _ = next(l1)
        l1.close()
        # resumed batch must differ from the consumed one (disjoint shards)
        assert not np.array_equal(b0["tokens"], b1["tokens"])

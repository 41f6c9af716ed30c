"""The scoring service's serve-scope counters on the tiny model: the padding's
fill that ``server-metrics`` exports is the one the prompts sent give."""
import json

import jax

import tiny
from harness import program, traffic
from harness.spec import BENCH

SEED = 2**33 + 21  # wider than 32 bits


def serve_counters(client) -> dict:
    from repro.core.flight import Action, batch_to_rows, decode_telemetry_batch

    rows = batch_to_rows(decode_telemetry_batch(
        client.do_action(Action("server-metrics", b""))[0].body))
    return {r["name"]: r["count"] for r in rows if r["scope"] == "serve"}


def test_token_fill_is_the_prompts_sent():
    from repro.core import RecordBatch
    from repro.core.flight import FlightClient, FlightDescriptor
    from repro.serving import LMScoringService

    conf = json.loads((BENCH / "configs" / "internlm2_1_8b.json").read_text())
    conf.update(tiny.TINY["configs/internlm2_1_8b.json"])
    mix = json.loads((BENCH / "traffic" / "single_poisson.json").read_text())
    mix.update(tiny.TINY["traffic/single_poisson.json"])
    max_seq = conf["service"]["max_seq"]
    prompts = traffic.open_loop(SEED, mix, 1.0, conf["model"]["vocab_size"])["prompts"]
    model, _ = program.build_model(conf)
    params, _ = model.init(jax.random.key(0))
    svc = LMScoringService(model, params, max_seq=max_seq).serve_tcp()
    try:
        client = FlightClient(f"tcp://127.0.0.1:{svc.port}")
        before = serve_counters(client)
        for p in prompts:  # one prompt per DoExchange call, as the cell sends them
            batch = RecordBatch.from_pydict({"tokens": [p.tolist()]})
            ex = client.do_exchange_stream(FlightDescriptor.for_path("score"), batch.schema)
            ex.feed([batch])
            assert len(list(ex)) == 1
            ex.close()
        after = serve_counters(client)
    finally:
        svc.shutdown()
    got = {k: after[k] - before[k] for k in ("requests", "tokens_real", "tokens_padded")}
    assert got == {"requests": len(prompts), "tokens_real": sum(len(p) for p in prompts),
                   "tokens_padded": len(prompts) * max_seq}

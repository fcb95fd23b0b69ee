"""Operations plane: ClientConfig, registry persistence, activation dumper,
warmup, env-flag table."""

import asyncio
import json
import os

import numpy as np
import pytest
import torch

import jax.numpy as jnp

from bloombee_tpu.client.config import ClientConfig
from bloombee_tpu.client.model import DistributedModelForCausalLM
from bloombee_tpu.server.block_server import BlockServer
from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=2, vocab_size=128,
        max_position_embeddings=128, tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    d = tmp_path_factory.mktemp("tiny_ops")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), config


def _server(model_dir, reg_port, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 4)
    return BlockServer(
        model_uid="tiny", start=0, end=2, model_dir=model_dir,
        registry=RegistryClient("127.0.0.1", reg_port), **kw,
    )


def test_client_config_blocked_servers(tiny):
    """ClientConfig.blocked_servers removes a peer from routing (reference
    config.py allowed/blocked servers)."""
    model_dir, config = tiny

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s1 = _server(model_dir, reg.port, throughput=10.0)
        s2 = _server(model_dir, reg.port, throughput=1.0)
        await s1.start()
        await s2.start()

        blocked = DistributedModelForCausalLM.from_pretrained(
            model_dir, RegistryClient("127.0.0.1", reg.port),
            model_uid="tiny",
            config=ClientConfig(blocked_servers=[s1.server_id]),
        )
        sess = blocked.inference_session(8, 1)
        await sess.__aenter__()
        used = {s.peer_id for s in (x.span for x in sess._spans)}
        await sess.__aexit__(None, None, None)
        assert used == {s2.server_id}  # best peer blocked -> other chosen

        await s1.stop()
        await s2.stop()
        await reg.stop()

    asyncio.run(run())


def test_registry_persistence_roundtrip(tmp_path):
    """A restarted registry reloads live records from its disk snapshot."""
    from bloombee_tpu.swarm.data import ServerInfo

    path = str(tmp_path / "registry.json")

    async def run():
        reg = RegistryServer(host="127.0.0.1", persist_path=path)
        await reg.start()
        client = RegistryClient("127.0.0.1", reg.port)
        info = ServerInfo(host="1.2.3.4", port=9, start_block=0, end_block=2)
        await client.declare_blocks(
            "m", "srv-a", range(0, 2), info, expiration=60.0
        )
        await client.close()
        await reg.stop()  # writes the final snapshot
        assert os.path.exists(path)

        reg2 = RegistryServer(host="127.0.0.1", persist_path=path)
        await reg2.start()
        client2 = RegistryClient("127.0.0.1", reg2.port)
        infos = await client2.get_module_infos("m", range(0, 2))
        assert all("srv-a" in mi.servers for mi in infos)
        assert infos[0].servers["srv-a"].host == "1.2.3.4"
        await client2.close()
        await reg2.stop()

    asyncio.run(run())


def test_activation_dumper(tiny, tmp_path, monkeypatch):
    model_dir, config = tiny
    dump_dir = str(tmp_path / "acts")
    monkeypatch.setenv("BBTPU_DUMP_ACTIVATIONS", dump_dir)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = _server(model_dir, reg.port)
        await s.start()
        model = DistributedModelForCausalLM.from_pretrained(
            model_dir, RegistryClient("127.0.0.1", reg.port), model_uid="tiny"
        )
        ids = np.arange(5)[None, :] % config.vocab_size
        await model.generate(ids, max_new_tokens=3)
        await s.stop()
        await reg.stop()

    asyncio.run(run())
    files = sorted(os.listdir(dump_dir))
    assert len(files) >= 3  # prefill + decode steps
    d = np.load(os.path.join(dump_dir, files[0]))
    assert {"hidden_in", "hidden_out", "start_block", "end_block"} <= set(d)


def test_warmup_compiles_buckets(tiny):
    model_dir, config = tiny

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = _server(model_dir, reg.port)
        await s.start()
        await s.warmup(batch_sizes=(1,), prefill_tokens=8)
        # cache must be fully released after warmup
        assert s.manager.tokens_left == s.manager.capacity_tokens
        await s.stop()
        await reg.stop()

    asyncio.run(run())


def test_env_describe_lists_declared_flags():
    from bloombee_tpu.utils import env

    table = env.describe()
    for name in ("BBTPU_MICROBATCH", "BBTPU_KV_QUANT",
                 "BBTPU_FLASH_ATTENTION", "BBTPU_DUMP_ACTIVATIONS",
                 "BBTPU_MIN_COMPRESS_BYTES"):
        assert name in table


def test_hub_resolve_download_cache_and_lru(tmp_path):
    """Hub-name resolution (reference from_pretrained.py:168-308 +
    disk_cache.py LRU): first use downloads via fetch_fn, second use hits
    the cache, and the LRU evicts the stalest snapshot under a byte budget."""
    from bloombee_tpu.models.hub import evict_lru, resolve_model_dir

    cache = str(tmp_path / "cache")
    calls = []

    def fake_fetch(name, dest):
        calls.append(name)
        os.makedirs(dest, exist_ok=True)
        with open(os.path.join(dest, "config.json"), "w") as f:
            json.dump({"model_type": "llama", "name": name}, f)
        with open(os.path.join(dest, "model.safetensors"), "wb") as f:
            f.write(b"x" * 1000)

    d1 = resolve_model_dir("org/model-a", cache_dir=cache,
                           max_cache_bytes=0, fetch_fn=fake_fetch)
    assert json.load(open(os.path.join(d1, "config.json")))["name"] == "org/model-a"
    d1_again = resolve_model_dir("org/model-a", cache_dir=cache,
                                 max_cache_bytes=0, fetch_fn=fake_fetch)
    assert d1 == d1_again and calls == ["org/model-a"]  # cache hit

    # local paths pass through untouched
    assert resolve_model_dir(d1, fetch_fn=fake_fetch) == d1

    # second model + a tight budget evicts the least recently used
    import time as _t

    _t.sleep(0.01)
    resolve_model_dir("org/model-b", cache_dir=cache, max_cache_bytes=0,
                      fetch_fn=fake_fetch)
    freed = evict_lru(cache, max_bytes=1500)
    assert freed > 0
    assert not os.path.exists(d1)  # model-a was stalest
    assert os.path.exists(os.path.join(cache, "org--model-b"))


def test_first_flag_read_from_many_threads_at_once():
    """A pure-client process reads its first switch when it opens its first
    session, and the benchmark's load generator opens one session a THREAD:
    the declaring modules are pulled in by one thread while the others wait
    (two threads importing one package at once: the loser saw it
    half-initialised, `cannot import name ... from partially initialized
    module`, and the run had no result)."""
    import subprocess
    import sys

    code = (
        "import threading\n"
        "from bloombee_tpu.utils import env\n"
        "n, errors = 8, []\n"
        "gate = threading.Barrier(n)\n"
        "def read():\n"
        "    gate.wait()\n"
        "    try:\n"
        "        env.get('BBTPU_PREFIX_CACHE')\n"
        "    except BaseException as e:\n"
        "        errors.append(repr(e))\n"
        "threads = [threading.Thread(target=read) for _ in range(n)]\n"
        "[t.start() for t in threads]\n"
        "[t.join() for t in threads]\n"
        "assert not errors, errors\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]

"""CLI surface end-to-end: run_registry + run_server as REAL subprocesses
(the documented deployment flow), then a client generate and the health
probe against them. The reference's equivalent is the manual live-swarm
tier (SURVEY.md §4: run_dht + run_server processes + pytest)."""

import asyncio
import os
import socket
import subprocess
import sys
import time

import numpy as np
import torch


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# every child is held to the CPU through its environment; this process (the
# pytest parent) is too, so no child ever waits on a device a parent holds
_CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _spawn(mod: str, args: list[str], log_path) -> subprocess.Popen:
    # log to a FILE, not a pipe: an undrained pipe blocks a chatty child
    # after ~64KB and stalls the swarm
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", f"bloombee_tpu.cli.{mod}", *args],
        stdout=log,
        stderr=subprocess.STDOUT,
        text=True,
        env=_CPU_ENV,
    )
    proc._log_path = log_path
    return proc


def _wait_port(port: int, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"port {port} never came up")


def test_cli_registry_server_client_health(tmp_path):
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=2, vocab_size=128,
        max_position_embeddings=128, tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "model")
    hf.save_pretrained(d, safe_serialization=True)

    reg_port = _free_port()
    procs = [
        _spawn("run_registry",
               ["--host", "127.0.0.1", "--port", str(reg_port)],
               tmp_path / "registry.log"),
    ]
    try:
        _wait_port(reg_port)  # registry must accept before servers announce
        for blocks in ("0:1", "1:2"):
            procs.append(
                _spawn(
                    "run_server",
                    [d, "--model-uid", "tiny", "--registry",
                     f"127.0.0.1:{reg_port}", "--blocks", blocks,
                     "--host", "127.0.0.1", "--public-host", "127.0.0.1",
                     "--num-pages", "32", "--page-size", "4",
                     "--dtype", "float32", "--warmup-batches", ""],
                    tmp_path / f"server{blocks.replace(':', '-')}.log",
                )
            )

        def _logs() -> str:
            return "\n".join(
                f"--- {p._log_path} ---\n"
                + open(p._log_path).read()[-2000:]
                for p in procs
            )

        # wait until the swarm covers both blocks
        from bloombee_tpu.swarm.registry import RegistryClient

        async def wait_complete():
            client = RegistryClient("127.0.0.1", reg_port)
            try:
                for _ in range(120):
                    for p in procs:
                        assert p.poll() is None, _logs()
                    try:
                        infos = await client.get_module_infos(
                            "tiny", range(2)
                        )
                        if all(mi.servers for mi in infos):
                            return
                    except Exception:
                        pass
                    await asyncio.sleep(0.5)
                raise TimeoutError(
                    "swarm never became complete\n" + _logs()
                )
            finally:
                await client.close()

        asyncio.run(wait_complete())

        # client generate through the CLI-launched swarm == HF greedy
        async def client_generate():
            from bloombee_tpu.client.model import DistributedModelForCausalLM

            reg_client = RegistryClient("127.0.0.1", reg_port)
            try:
                model = DistributedModelForCausalLM.from_pretrained(
                    d, reg_client, model_uid="tiny"
                )
                ids_in = np.arange(6)[None, :] % config.vocab_size
                return await model.generate(ids_in, max_new_tokens=5)
            finally:
                await reg_client.close()

        ids = asyncio.run(client_generate())
        with torch.no_grad():
            prompt = torch.tensor(np.arange(6)[None, :] % config.vocab_size)
            ref = hf.generate(
                prompt, attention_mask=torch.ones_like(prompt),
                max_new_tokens=5, do_sample=False,
            ).numpy()
        # HF may stop early at its eos token; the generated prefix must match
        assert ref.shape[1] > prompt.shape[1]
        np.testing.assert_array_equal(ids[:, : ref.shape[1]], ref)

        # ONE health invocation, in probe mode, after real traffic: sees
        # the complete swarm AND must surface the wire-path counters —
        # bytes shipped vs raw (the bytes/token floor) and the off-loop
        # codec pipeline state, the BB006 no-log-access operator surface
        probe = subprocess.run(
            [sys.executable, "-m", "bloombee_tpu.cli.health",
             "tiny", "--num-blocks", "2", "--registry",
             f"127.0.0.1:{reg_port}", "--probe"],
            capture_output=True, text=True, timeout=60, env=_CPU_ENV,
        )
        assert "COMPLETE" in probe.stdout, probe.stdout + probe.stderr
        assert "[reachable]" in probe.stdout, probe.stdout
        assert "tx_wire_bytes=" in probe.stdout, probe.stdout
        assert "pipeline=on" in probe.stdout, probe.stdout
        assert "rx_jobs=" in probe.stdout, probe.stdout
        # a session's turn, leg by leg, by the step's class
        assert "turn.prefill n=1 away=" in probe.stdout, probe.stdout
        assert "turn.decode n=" in probe.stdout, probe.stdout
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

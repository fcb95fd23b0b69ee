"""Speculative decoding: tree math, acceptance rules, and e2e equivalence.

Ports the intent of /root/reference/tests/test_spe_dec_tree.py,
test_spec_decoding_verify.py, test_speculative_generation.py. The e2e
invariant: greedy speculative decode produces EXACTLY the tokens of plain
greedy decode.
"""

import asyncio

import numpy as np
import pytest
import torch

import jax.numpy as jnp

from bloombee_tpu.spec.tree import DraftTree, chain_tree, tree_attention_mask
from bloombee_tpu.spec.verify import accept_greedy, accept_sampling


def test_tree_invariants():
    #       0   1          (roots)
    #      2 3   4
    #      5
    tree = DraftTree(
        tokens=np.asarray([10, 11, 12, 13, 14, 15]),
        parents=np.asarray([-1, -1, 0, 0, 1, 2]),
    )
    assert tree.depths().tolist() == [0, 0, 1, 1, 1, 2]
    a = tree.ancestors_or_self()
    assert a[5].tolist() == [True, False, True, False, False, True]
    assert tree.path_to(5) == [0, 2, 5]
    assert tree.children_of(-1).tolist() == [0, 1]
    assert tree.children_of(0).tolist() == [2, 3]
    m = tree_attention_mask(tree)
    assert m.shape == (6, 6)
    assert not m[2, 1]  # sibling branch invisible

    with pytest.raises(ValueError):
        DraftTree(tokens=np.asarray([1, 2]), parents=np.asarray([1, -1]))

    chain = chain_tree(np.asarray([5, 6, 7]))
    assert chain.parents.tolist() == [-1, 0, 1]
    assert np.all(chain.ancestors_or_self() == np.tril(np.ones((3, 3), bool)))


def _logits_for(vocab, *winners):
    """[len(winners), vocab] logits whose argmax at row i is winners[i]."""
    out = np.zeros((len(winners), vocab), np.float32)
    for i, w in enumerate(winners):
        out[i, w] = 5.0
    return out


def test_accept_greedy_path():
    # tree: 0(tok 3) -> 1(tok 7) -> 2(tok 9); sibling 3(tok 8) under 0
    tree = DraftTree(
        tokens=np.asarray([3, 7, 9, 8]),
        parents=np.asarray([-1, 0, 1, 0]),
    )
    vocab = 16
    root_logits = _logits_for(vocab, 3)[0]  # target wants 3 -> accept node 0
    logits = _logits_for(vocab, 7, 9, 1, 0)  # node0->7, node1->9, node2->1
    accepted, bonus = accept_greedy(tree, root_logits, logits)
    assert accepted == [0, 1, 2]
    assert bonus == 1  # argmax after node 2

    # target disagrees at the root: nothing accepted, bonus = target's pick
    accepted, bonus = accept_greedy(tree, _logits_for(vocab, 5)[0], logits)
    assert accepted == [] and bonus == 5

    # target accepts node 0 then picks the sibling branch (node 3, tok 8)
    logits2 = _logits_for(vocab, 8, 9, 1, 2)  # node0 -> 8 => descend to 3
    accepted, bonus = accept_greedy(
        tree, _logits_for(vocab, 3)[0], logits2
    )
    assert accepted == [0, 3] and bonus == 2


def test_accept_sampling_peaked_matches_greedy():
    tree = DraftTree(
        tokens=np.asarray([3, 7]), parents=np.asarray([-1, 0])
    )
    vocab = 8
    root_logits = _logits_for(vocab, 3)[0] * 10
    logits = _logits_for(vocab, 7, 2)[:2] * 10
    draft_probs = np.full((2, vocab), 1e-3)
    draft_probs[0, 3] = 1.0
    draft_probs[1, 7] = 1.0
    rng = np.random.default_rng(0)
    accepted, bonus = accept_sampling(
        tree, root_logits, logits, draft_probs, rng, temperature=1.0
    )
    assert accepted == [0, 1] and bonus == 2


def test_e2e_speculative_equals_greedy(tmp_path):
    from transformers import LlamaConfig, LlamaForCausalLM

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "model")
    hf.save_pretrained(d, safe_serialization=True)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        servers = [
            BlockServer(model_uid="m", start=0, end=2, model_dir=d,
                        registry=rc(), compute_dtype=jnp.float32,
                        num_pages=64, page_size=4),
            BlockServer(model_uid="m", start=2, end=3, model_dir=d,
                        registry=rc(), compute_dtype=jnp.float32,
                        num_pages=64, page_size=4),
        ]
        for s in servers:
            await s.start()

        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="m", use_push=False
        )
        # the model drafts for itself -> high acceptance, exact equality
        drafter = GreedyTreeDrafter(
            LocalJaxDraftModel.from_dir(d), branching=(2, 1)
        )
        input_ids = np.arange(5)[None, :]
        n_new = 10

        spec_ids = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=n_new
        )
        # may overshoot by the accepted path length; the generated prefix
        # must match plain greedy token-for-token
        assert spec_ids.shape[1] >= input_ids.shape[1] + n_new
        plain_ids = await model.generate(
            input_ids, max_new_tokens=spec_ids.shape[1] - input_ids.shape[1]
        )
        np.testing.assert_array_equal(spec_ids, plain_ids)

        for s in servers:
            await s.stop()
        await reg.stop()

    asyncio.run(run())


def test_e2e_speculative_batch4_equals_greedy(tmp_path):
    """Batched speculative decoding (reference speculative_model.py:33-117
    per-sample trees): 4 rows with different prompts, per-row accepts, all
    token-exact vs plain batched greedy."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    import jax.numpy as jnp

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "model")
    hf.save_pretrained(d, safe_serialization=True)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        servers = [
            BlockServer(model_uid="m", start=0, end=2, model_dir=d,
                        registry=rc(), compute_dtype=jnp.float32,
                        num_pages=256, page_size=4),
            BlockServer(model_uid="m", start=2, end=3, model_dir=d,
                        registry=rc(), compute_dtype=jnp.float32,
                        num_pages=256, page_size=4),
        ]
        for s in servers:
            await s.start()

        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="m", use_push=False
        )
        drafter = GreedyTreeDrafter(
            LocalJaxDraftModel.from_dir(d), branching=(2, 1)
        )
        rng = np.random.default_rng(7)
        input_ids = rng.integers(0, 128, size=(4, 5))
        n_new = 8

        spec_ids = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=n_new
        )
        assert spec_ids.shape == (4, 5 + n_new)
        plain_ids = await model.generate(input_ids, max_new_tokens=n_new)
        np.testing.assert_array_equal(spec_ids, plain_ids)

        for s in servers:
            await s.stop()
        await reg.stop()

    asyncio.run(run())


def test_e2e_speculative_failover_ragged_replay(tmp_path):
    """Kill the preferred tail server between two batched speculative calls
    on one session: recovery replays RAGGED per-row token ids (rows committed
    different counts) and continuation stays token-exact vs plain greedy."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    import jax.numpy as jnp

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "model")
    hf.save_pretrained(d, safe_serialization=True)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        s_a = BlockServer(model_uid="m", start=0, end=2, model_dir=d,
                          registry=rc(), compute_dtype=jnp.float32,
                          num_pages=256, page_size=4, throughput=10.0)
        s_b = BlockServer(model_uid="m", start=2, end=3, model_dir=d,
                          registry=rc(), compute_dtype=jnp.float32,
                          num_pages=256, page_size=4, throughput=10.0)
        s_c = BlockServer(model_uid="m", start=2, end=3, model_dir=d,
                          registry=rc(), compute_dtype=jnp.float32,
                          num_pages=256, page_size=4, throughput=1.0)
        for s in (s_a, s_b, s_c):
            await s.start()

        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="m", use_push=False
        )
        drafter = GreedyTreeDrafter(
            LocalJaxDraftModel.from_dir(d), branching=(2, 1)
        )
        rng = np.random.default_rng(11)
        input_ids = rng.integers(0, 128, size=(3, 5))
        session = model.inference_session(64, 3)
        await session.__aenter__()
        used = {x.span.server_info.port for x in session._spans}
        assert s_b.port in used and s_c.port not in used

        first = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=5, session=session
        )
        # rows committed ragged counts; kill the preferred tail server
        await s_b.stop()
        more = await generate_speculative(
            model, drafter, first[:, -1:], max_new_tokens=5, session=session
        )
        await session.__aexit__(None, None, None)
        final = np.concatenate([first, more[:, 1:]], axis=1)
        plain = await model.generate(input_ids, max_new_tokens=10)
        np.testing.assert_array_equal(final, plain)

        for s in (s_a, s_c):
            await s.stop()
        await reg.stop()

    asyncio.run(run())


def test_e2e_speculative_pruned_midchain(tmp_path):
    """Mid-chain pruning (reference backend.py:395-410 + client restore):
    span 0 keeps only MidLMHead survivors, downstream spans verify the
    smaller tree, the client restores kept logits to original indices —
    tokens stay exactly equal to plain greedy."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    import jax.numpy as jnp

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "model")
    hf.save_pretrained(d, safe_serialization=True)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        s1 = BlockServer(model_uid="m", start=0, end=2, model_dir=d,
                         registry=rc(), compute_dtype=jnp.float32,
                         num_pages=256, page_size=4)
        s2 = BlockServer(model_uid="m", start=2, end=3, model_dir=d,
                         registry=rc(), compute_dtype=jnp.float32,
                         num_pages=256, page_size=4)
        await s1.start()
        await s2.start()

        keeps = []
        orig_prune = s1._prune_tree

        def spy(out, prune):
            k = orig_prune(out, prune)
            keeps.append(k)
            return k

        s1._prune_tree = spy

        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="m", use_push=False
        )
        drafter = GreedyTreeDrafter(
            LocalJaxDraftModel.from_dir(d), branching=(2, 2)
        )
        rng = np.random.default_rng(5)
        input_ids = rng.integers(0, 128, size=(2, 5))
        n_new = 8

        spec_ids = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=n_new,
            prune_threshold=0.45,
        )
        assert spec_ids.shape == (2, 5 + n_new)
        plain_ids = await model.generate(input_ids, max_new_tokens=n_new)
        np.testing.assert_array_equal(spec_ids, plain_ids)
        # the pruner actually ran and dropped nodes in at least one round
        assert keeps, "server-side pruner never invoked"
        assert any(
            k is not None and (k < 0).any() for k in keeps
        ), "pruner never dropped a node (threshold too low for this test)"

        await s1.stop()
        await s2.stop()
        await reg.stop()

    asyncio.run(run())


def test_drafter_cached_matches_uncached():
    """The prefix-KV cached drafter must build exactly the trees the
    recompute-everything path built (same top-k expansions)."""
    import jax
    import jax.numpy as jnp

    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.utils.tree import unstack_params

    spec = ModelSpec(
        family="llama", hidden_size=32, intermediate_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_hidden_layers=2, vocab_size=64,
    )
    blocks = [
        init_block_params(jax.random.PRNGKey(i), spec) for i in range(2)
    ]
    rng = jax.random
    client = {
        "embed": rng.normal(rng.PRNGKey(7), (64, 32)) * 0.1,
        "norm": jnp.ones((32,)),
        "lm_head": rng.normal(rng.PRNGKey(8), (32, 64)) * 0.1,
    }
    model = LocalJaxDraftModel(spec, blocks, client)
    drafter = GreedyTreeDrafter(model, branching=(2, 2, 1))
    contexts = [[1, 5, 9, 2], [3, 3, 3, 3, 3, 7]]

    trees, probs = drafter.build_batch(contexts)

    # uncached reference: full recompute per level via last_logits_ragged
    def build_uncached(ctx):
        tokens, parents = [], []
        frontier = [(-1, list(ctx))]
        for width in drafter.branching:
            seqs = [f[1] for f in frontier]
            logits = model.last_logits_ragged(seqs)
            top = np.argsort(-logits, axis=-1)[:, :width]
            new_frontier = []
            for fi, (parent, path) in enumerate(frontier):
                for tok in top[fi]:
                    idx = len(tokens)
                    tokens.append(int(tok))
                    parents.append(parent)
                    new_frontier.append((idx, path + [int(tok)]))
            frontier = new_frontier
        return tokens, parents

    # numerical agreement first (the robust contract: cached and uncached
    # attention reduce in different orders, so logits match to tolerance)
    l_cached = model.prefill_ragged(contexts)[2]
    l_uncached = model.last_logits_ragged(contexts)
    np.testing.assert_allclose(l_cached, l_uncached, atol=1e-4, rtol=1e-4)
    for r, ctx in enumerate(contexts):
        ref_tokens, ref_parents = build_uncached(ctx)
        np.testing.assert_array_equal(trees[r].tokens, ref_tokens)
        np.testing.assert_array_equal(trees[r].parents, ref_parents)


def test_shape_chooser_prefers_depth_when_accepts_are_high():
    from bloombee_tpu.spec.shape import (
        AcceptanceStats,
        choose_branching,
        expected_accepted,
        tree_nodes,
    )

    assert tree_nodes((2, 2, 1)) == 11

    hot = AcceptanceStats()
    cold = AcceptanceStats()
    for _ in range(200):
        hot.observe(3, (2, 2, 2))   # everything accepts
        cold.observe(0, (2, 2, 2))  # nothing ever accepts
    deep, shallow = (2, 2, 2), (4,)
    assert expected_accepted(deep, hot) > expected_accepted(shallow, hot)
    chosen_hot = choose_branching(hot, budget_nodes=15)
    chosen_cold = choose_branching(cold, budget_nodes=15)
    assert len(chosen_hot) >= 2  # deep pays off when accepts are high
    assert tree_nodes(chosen_cold) <= tree_nodes(chosen_hot)


def test_e2e_adaptive_drafter_stays_exact(tmp_path):
    """Adaptive tree shaping retunes branching mid-generation; tokens must
    stay exactly greedy."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    import jax.numpy as jnp

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "model")
    hf.save_pretrained(d, safe_serialization=True)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = BlockServer(model_uid="m", start=0, end=3, model_dir=d,
                        registry=RegistryClient("127.0.0.1", reg.port),
                        compute_dtype=jnp.float32, num_pages=256,
                        page_size=4)
        await s.start()
        model = DistributedModelForCausalLM.from_pretrained(
            d, RegistryClient("127.0.0.1", reg.port), model_uid="m"
        )
        drafter = GreedyTreeDrafter(
            LocalJaxDraftModel.from_dir(d), branching=(2, 2),
            adaptive=True, retune_every=2,
        )
        input_ids = np.arange(5)[None, :]
        n_new = 14
        spec_ids = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=n_new
        )
        plain_ids = await model.generate(input_ids, max_new_tokens=n_new)
        np.testing.assert_array_equal(spec_ids, plain_ids)
        assert drafter.stats.tries.sum() > 0  # feedback actually flowed
        await s.stop()
        await reg.stop()

    asyncio.run(run())


def test_e2e_speculative_sampling(tmp_path):
    """Sampling-mode speculative decode (SpecInfer rejection sampling): at
    near-zero temperature it equals greedy; at temperature 1 it runs and is
    reproducible per seed."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    import jax.numpy as jnp

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "model")
    hf.save_pretrained(d, safe_serialization=True)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = BlockServer(model_uid="m", start=0, end=3, model_dir=d,
                        registry=RegistryClient("127.0.0.1", reg.port),
                        compute_dtype=jnp.float32, num_pages=256,
                        page_size=4)
        await s.start()
        model = DistributedModelForCausalLM.from_pretrained(
            d, RegistryClient("127.0.0.1", reg.port), model_uid="m"
        )
        drafter = GreedyTreeDrafter(
            LocalJaxDraftModel.from_dir(d), branching=(2, 1)
        )
        input_ids = np.arange(2 * 5).reshape(2, 5) % 120
        n_new = 6

        cold = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=n_new,
            do_sample=True, temperature=1e-4, seed=0,
        )
        greedy = await model.generate(input_ids, max_new_tokens=n_new)
        np.testing.assert_array_equal(cold, greedy)

        hot1 = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=n_new,
            do_sample=True, temperature=1.0, seed=7,
        )
        hot2 = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=n_new,
            do_sample=True, temperature=1.0, seed=7,
        )
        assert hot1.shape == (2, 5 + n_new)
        np.testing.assert_array_equal(hot1, hot2)  # seed-reproducible

        await s.stop()
        await reg.stop()

    asyncio.run(run())


def test_accept_sampling_preserves_target_distribution():
    """The emitted token (accepted draft or bonus) must be distributed
    exactly as softmax(target/T), with DETERMINISTIC top-k proposals — the
    way our drafter actually proposes (the SpecInfer min(1,p/q) rule would
    be biased here)."""
    from bloombee_tpu.spec.verify import _softmax

    vocab = 6
    rng0 = np.random.default_rng(42)
    target_logits = rng0.normal(size=vocab) * 1.5
    drafter_logits = rng0.normal(size=vocab) * 1.5
    top2 = np.argsort(-drafter_logits)[:2]  # deterministic proposals
    for temperature in (1.0, 0.5):
        counts = np.zeros(vocab)
        n = 40000
        rng = np.random.default_rng(0)
        tree = DraftTree(
            tokens=np.asarray(top2), parents=np.asarray([-1, -1])
        )
        dummy = np.zeros((2, vocab), np.float32)
        for _ in range(n):
            accepted, bonus = accept_sampling(
                tree, target_logits, dummy, _softmax(drafter_logits[None]),
                rng, temperature=temperature,
            )
            tok = int(tree.tokens[accepted[0]]) if accepted else bonus
            counts[tok] += 1
        emp = counts / n
        tgt = _softmax(target_logits[None] / temperature)[0]
        tv = 0.5 * np.abs(emp - tgt).sum()
        assert tv < 0.02, (temperature, tv, emp.round(3), tgt.round(3))


def test_e2e_speculative_qwen2_family(tmp_path):
    """Non-llama family drafting + tree-verifying through the swarm: the
    drafter registry is family-generic (round-4 verdict: it hardwired
    llama's block_forward). Qwen2 brings biased qkv projections."""
    import transformers as tf

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    config = tf.Qwen2Config(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=2, vocab_size=128,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
    )
    torch.manual_seed(4)
    hf = tf.Qwen2ForCausalLM(config).eval().to(torch.float32)
    d = str(tmp_path / "qwen2")
    hf.save_pretrained(d, safe_serialization=True)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        server = BlockServer(
            model_uid="q", start=0, end=2, model_dir=d, registry=rc(),
            compute_dtype=jnp.float32, num_pages=64, page_size=4,
        )
        await server.start()

        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="q", use_push=False
        )
        drafter = GreedyTreeDrafter(
            LocalJaxDraftModel.from_dir(d), branching=(2, 1)
        )
        input_ids = np.arange(5)[None, :]
        spec_ids = await generate_speculative(
            model, drafter, input_ids, max_new_tokens=8
        )
        assert spec_ids.shape[1] >= input_ids.shape[1] + 8
        plain_ids = await model.generate(
            input_ids, max_new_tokens=spec_ids.shape[1] - input_ids.shape[1]
        )
        np.testing.assert_array_equal(spec_ids, plain_ids)

        await server.stop()
        await reg.stop()

    asyncio.run(run())


def test_drafter_rejects_unsupported_family():
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.spec.drafter import LocalJaxDraftModel

    spec = ModelSpec(
        family="bloom", hidden_size=32, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=4, head_dim=8,
        num_hidden_layers=2, vocab_size=64, alibi=True, norm_type="ln",
        mlp_type="gelu_tanh",
    )
    with pytest.raises(NotImplementedError, match="ALiBi"):
        LocalJaxDraftModel(spec, [], {})


# ---------------------------------------------- batched tree verification
def _save_tiny_llama(path, seed=0):
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(seed)
    hf = LlamaForCausalLM(config).eval().to(torch.float32)
    hf.save_pretrained(str(path), safe_serialization=True)
    return str(path), hf, config


def _hf_greedy(hf_model, input_ids, max_new_tokens):
    with torch.no_grad():
        out = hf_model.generate(
            torch.tensor(np.asarray(input_ids)),
            max_new_tokens=max_new_tokens, do_sample=False, use_cache=True,
        )
    return out.numpy()


def test_e2e_spec_batch_concurrent_sessions_token_identical(
    tmp_path, monkeypatch
):
    """Two concurrently speculating sessions on a --spec-batch server
    coalesce their tree-verify steps into shared ragged dispatches
    (tree_group_dispatches > 0, width ~2) and stay token-identical to a
    solo-sequential speculative run AND to HF greedy. Session A carries 3
    rows drafted by a DIFFERENT tiny model (low, uneven acceptance), so
    rows finish at different rounds and the client's live-row window
    exercises `rows` slices on tree steps."""
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
    from bloombee_tpu.wire.rpc import connect

    d, hf, config = _save_tiny_llama(tmp_path / "model", seed=0)
    d2, _, _ = _save_tiny_llama(tmp_path / "drafter", seed=1)
    rng = np.random.default_rng(19)
    prompts = [
        rng.integers(0, config.vocab_size, size=(3, 5)),
        rng.integers(0, config.vocab_size, size=(1, 6)),
    ]
    drafter_dirs = [d2, d]  # weak drafter for A (ragged finishes), self for B
    n_new = 8

    async def run_spec(spec_batch, window):
        monkeypatch.setenv("BBTPU_BATCH_WINDOW_MS", window)
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        s = BlockServer(model_uid="m", start=0, end=3, model_dir=d,
                        registry=rc(), compute_dtype=jnp.float32,
                        num_pages=256, page_size=4, max_batch=8,
                        spec_batch=spec_batch)
        await s.start()
        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="m"
        )
        info = None
        try:
            coros = [
                generate_speculative(
                    model,
                    GreedyTreeDrafter(
                        LocalJaxDraftModel.from_dir(dd), branching=(2, 1)
                    ),
                    p, max_new_tokens=n_new,
                )
                for p, dd in zip(prompts, drafter_dirs)
            ]
            if spec_batch:
                outs = await asyncio.gather(*coros)
            else:
                outs = [await c for c in coros]
            conn = await connect("127.0.0.1", s.port)
            info, _ = await conn.call("rpc_info", {})
            await conn.close()
        finally:
            await s.stop()
            await reg.stop()
        return [np.asarray(o) for o in outs], s, info

    # window > client think-time (drafter forward, ~0.5s/round on CPU):
    # a tighter window lets the sessions phase-lock and never group
    batched, s_b, info = asyncio.run(run_spec(True, "2000"))
    solo, s_u, _ = asyncio.run(run_spec(False, "0"))

    # the batched run really coalesced; the flag-off run never did
    assert s_b.tree_group_dispatches > 0
    assert s_u.tree_group_dispatches == 0
    assert s_b.tree_steps > 0 and s_u.tree_steps > 0
    # the same tokens from fewer device dispatches: a grouped round is ONE
    # dispatch where the solo run pays one per session
    assert s_b.step_dispatches < s_u.step_dispatches

    for got_b, got_u, p in zip(batched, solo, prompts):
        np.testing.assert_array_equal(got_b, got_u)
        ref = _hf_greedy(hf, p, got_b.shape[1] - p.shape[1])
        np.testing.assert_array_equal(got_b, ref)

    # observability: the new spec counters surface in rpc_info
    assert info["spec_batch"] is True
    assert info["tree_group_dispatches"] == s_b.tree_group_dispatches
    assert info["mean_tree_batch_width"] >= 2.0
    assert info["tree_steps"] == s_b.tree_steps
    assert info["spec_tokens_drafted"] > 0
    assert 0.0 < info["spec_accept_rate"] <= 1.0
    sess_spec = info["session_spec"]
    assert len(sess_spec) == 2
    for entry in sess_spec.values():
        assert entry["drafted"] > 0
        assert 0.0 <= entry["accept_rate"] <= 1.0
    # the self-drafted session accepts nearly everything; the weak-drafted
    # one does not — per-session rates really are measured per session
    rates = sorted(e["accept_rate"] for e in sess_spec.values())
    assert rates[1] > rates[0]


@pytest.mark.chaos
def test_e2e_spec_batch_fault_mid_verify_replays_solo(
    tmp_path, monkeypatch
):
    """A group dispatch that fails AFTER the device step wrote every
    member's tree rows must roll all members back to their pre-dispatch
    lengths and replay them solo — tokens stay exactly HF greedy."""
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    d, hf, config = _save_tiny_llama(tmp_path / "model", seed=0)
    # the window must exceed client think-time (drafter forward ~0.5s on
    # CPU here), else the two sessions phase-lock anti-phase and never group
    monkeypatch.setenv("BBTPU_BATCH_WINDOW_MS", "2000")
    # JIT compiles block the event loop for 10-15s at a time here (the
    # solo-replay tree shapes compile fresh after the injected fault), so
    # any keepalive fence the ambient chaos matrix configures fires during
    # a stall and takes down every loopback conn at once — including the
    # registry announce, which fail-louds recovery with MissingBlocksError.
    # An injected half-open partition is conversely undetectable without
    # keepalives and hangs the run. Both knobs are orthogonal to what this
    # test targets (group rollback + solo replay token-exactness) and have
    # dedicated coverage in test_session_lease, so strip them while keeping
    # the rest of the ambient chaos (delays, resets). The fault plan is
    # built lazily once per process, so reset its cache to pick up the env.
    from bloombee_tpu.wire import faults

    monkeypatch.setenv("BBTPU_KEEPALIVE_S", "0")
    monkeypatch.setenv("BBTPU_CHAOS_PARTITION_P", "0")
    monkeypatch.setattr(faults, "_env_checked", False)
    monkeypatch.setattr(faults, "_active_plan", None)
    rng = np.random.default_rng(23)
    prompts = [
        rng.integers(0, config.vocab_size, size=(1, 5)) for _ in range(2)
    ]
    n_new = 8

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        s = BlockServer(model_uid="m", start=0, end=3, model_dir=d,
                        registry=rc(), compute_dtype=jnp.float32,
                        num_pages=256, page_size=4, max_batch=8,
                        spec_batch=True)
        await s.start()

        # fail the FIRST group dispatch after its speculative KV writes
        # landed: recovery must truncate every member before the solo
        # replay. Group dispatches all flow through the universal
        # ragged_group entry point, so that's the interposition surface.
        orig = s.executor.ragged_group
        calls = {"n": 0}

        def flaky(*a, **kw):
            out = orig(*a, **kw)
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected fault after device dispatch")
            return out

        s.executor.ragged_group = flaky

        # ambient chaos (CORRUPT entry) can corrupt a span-output reply of
        # this test too: the digest reject takes the standard short fault
        # ban, and in a ONE-server swarm the default 15s ban outlasts the
        # default 3-attempt recovery budget no matter what. Short bans +
        # a generous retry budget keep that heal structurally survivable
        # (and the token-identity assertion still covers it) without
        # stripping corruption from the ambient plan.
        from bloombee_tpu.client.config import ClientConfig

        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="m",
            config=ClientConfig(
                max_retries=10, ban_timeout=0.5, ban_max=2.0,
            ),
        )
        try:
            outs = await asyncio.gather(*(
                generate_speculative(
                    model,
                    GreedyTreeDrafter(
                        LocalJaxDraftModel.from_dir(d), branching=(2, 1)
                    ),
                    p, max_new_tokens=n_new,
                )
                for p in prompts
            ))
            assert calls["n"] >= 1, "no group dispatch ever formed"
            assert s.batch_solo_steps >= 2  # both members replayed solo
            for p, got in zip(prompts, outs):
                got = np.asarray(got)
                ref = _hf_greedy(hf, p, got.shape[1] - p.shape[1])
                np.testing.assert_array_equal(got, ref)
        finally:
            await s.stop()
            await reg.stop()

    asyncio.run(run())


def test_e2e_spec_batch_after_prefix_adoption(tmp_path, monkeypatch):
    """Prefix adoption composes with batched tree verification: a cold
    session publishes a shared prompt prefix; two later speculating
    sessions (one adopting that prefix) group their tree-verify steps and
    stay HF-exact."""
    from bloombee_tpu.client.config import ClientConfig
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    d, hf, config = _save_tiny_llama(tmp_path / "model", seed=0)
    # window > client think-time (drafter forward ~0.5s on CPU), else the
    # two identically-paced sessions phase-lock and never share a window
    monkeypatch.setenv("BBTPU_BATCH_WINDOW_MS", "2000")
    shared = (np.arange(8)[None, :] * 7 + 1) % config.vocab_size
    long_ids = np.concatenate(
        [shared, (np.arange(8)[None, :] * 3 + 2) % config.vocab_size],
        axis=1,
    )
    other = np.random.default_rng(29).integers(
        0, config.vocab_size, size=(1, 6)
    )
    n_new = 6

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        s = BlockServer(model_uid="m", start=0, end=3, model_dir=d,
                        registry=rc(), compute_dtype=jnp.float32,
                        num_pages=256, page_size=4, max_batch=8,
                        spec_batch=True, prefix_cache=True)
        await s.start()
        model = DistributedModelForCausalLM.from_pretrained(
            d, rc(), model_uid="m",
            config=ClientConfig(use_push=False, prefix_cache=True),
        )

        def drafter():
            return GreedyTreeDrafter(
                LocalJaxDraftModel.from_dir(d), branching=(2, 1)
            )

        try:
            # cold pass publishes the shared prefix pages
            cold = await generate_speculative(
                model, drafter(), shared, max_new_tokens=n_new
            )
            ref = _hf_greedy(hf, shared, cold.shape[1] - shared.shape[1])
            np.testing.assert_array_equal(cold, ref)

            outs = await asyncio.gather(
                generate_speculative(
                    model, drafter(), long_ids, max_new_tokens=n_new
                ),
                generate_speculative(
                    model, drafter(), other, max_new_tokens=n_new
                ),
            )
            for p, got in zip((long_ids, other), outs):
                got = np.asarray(got)
                ref = _hf_greedy(hf, p, got.shape[1] - p.shape[1])
                np.testing.assert_array_equal(got, ref)
            assert s.manager.prefix_stats()["prefix_hits"] >= 1
            assert s.tree_group_dispatches > 0
        finally:
            await s.stop()
            await reg.stop()

    asyncio.run(run())


def test_drafter_autotune_shrinks_on_acceptance_collapse():
    """Closed feedback loop, collapse direction: when observed acceptance
    goes to zero, the adaptive chooser's per-node cost makes every node a
    net loss and the tree shrinks monotonically to the smallest candidate;
    the drafter's measured accept_rate tracks the collapse."""
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter
    from bloombee_tpu.spec.shape import tree_nodes

    drafter = GreedyTreeDrafter(
        model=None, branching=(2, 2, 2), adaptive=True, retune_every=1
    )
    assert drafter.accept_rate == 0.0  # nothing observed yet
    drafter.observe([3, 3])  # one warm round: everything accepted
    assert drafter.accept_rate == 1.0

    for _ in range(3):
        drafter.observe([0, 0])  # collapse reaches every level's stats
    sizes = [tree_nodes(drafter.branching)]
    for _ in range(40):
        drafter.observe([0, 0])  # sustained acceptance collapse
        sizes.append(tree_nodes(drafter.branching))
    assert all(b <= a for a, b in zip(sizes, sizes[1:])), sizes
    assert sizes[-1] < sizes[0]
    assert sizes[-1] == min(
        tree_nodes(c) for c in ((2,), (4,), (2, 1), (2, 2))
    )  # collapsed all the way to the cheapest viable candidate
    assert drafter.accept_rate < 0.1

    # recovery direction: sustained full accepts regrow the tree
    deep = GreedyTreeDrafter(
        model=None, branching=(2, 2, 2), adaptive=True, retune_every=1
    )
    for _ in range(40):
        deep.observe([3, 3])
    assert len(deep.branching) >= 2
    assert deep.accept_rate == 1.0

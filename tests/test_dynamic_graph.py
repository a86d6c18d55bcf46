"""Dynamic graph model (§3.2): mask module + position attribute semantics,
plus the property suite over churn (``perturb_scenario`` /
``add_users`` / ``remove_users`` / the fault-event waves): adjacency stays
symmetric with a zero diagonal, inactive rows/columns carry no edges, and
``num_active`` always equals the mask population."""
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st
from repro.core.dynamic_graph import (EVENT_ARRIVE, EVENT_DEPART, GraphEvent,
                                      GraphState, add_users, apply_user_event,
                                      arrival_wave, departure_wave,
                                      make_graph_state, move_users,
                                      perturb_scenario, random_scenario,
                                      remove_users, rewire)


def test_make_graph_state_masks_and_pads():
    st = make_graph_state(8, [[0, 0], [1, 1], [2, 2]], [(0, 1), (1, 2)],
                          [10, 20, 30])
    assert float(st.num_active()) == 3
    assert st.adj.shape == (8, 8)
    assert float(st.adj[0, 1]) == 1.0 and float(st.adj[1, 0]) == 1.0
    assert float(st.task_kb[3]) == 0.0           # padded slot empty


def test_remove_users_drops_edges():
    st = make_graph_state(4, np.zeros((4, 2)), [(0, 1), (1, 2), (2, 3)],
                          [1, 1, 1, 1])
    st2 = remove_users(st, jnp.asarray([0.0, 1.0, 0.0, 0.0]))
    assert float(st2.num_active()) == 3
    assert float(st2.adj[0, 1]) == 0.0 and float(st2.adj[1, 2]) == 0.0
    assert float(st2.adj[2, 3]) == 1.0           # untouched edge survives


def test_add_users_reuses_masked_slots():
    st = make_graph_state(4, np.zeros((3, 2)), [(0, 1)], [1, 1, 1], active=3)
    st = remove_users(st, jnp.asarray([0.0, 1.0, 0.0, 0.0]))
    adj_new = np.zeros((4, 4), np.float32)
    adj_new[1, 2] = adj_new[2, 1] = 1.0
    st2 = add_users(st, jnp.asarray([0.0, 1.0, 0.0, 0.0]),
                    jnp.asarray(np.full((4, 2), 7.0, np.float32)),
                    jnp.asarray(np.full(4, 42.0, np.float32)),
                    jnp.asarray(adj_new))
    assert float(st2.num_active()) == 3
    assert float(st2.task_kb[1]) == 42.0
    assert float(st2.pos[1, 0]) == 7.0
    assert float(st2.adj[1, 2]) == 1.0


def test_add_cannot_clobber_active_slot():
    st = make_graph_state(3, np.zeros((3, 2)), [], [1, 2, 3])
    st2 = add_users(st, jnp.ones(3), jnp.asarray(np.full((3, 2), 9.0,
                                                         np.float32)),
                    jnp.asarray(np.full(3, 99.0, np.float32)),
                    st.adj)
    np.testing.assert_allclose(np.asarray(st2.task_kb),
                               np.asarray(st.task_kb))


def test_move_users_only_moves_active():
    st = make_graph_state(3, np.zeros((2, 2)), [], [1, 1], active=2)
    newp = jnp.asarray(np.full((3, 2), 5.0, np.float32))
    st2 = move_users(st, newp)
    assert float(st2.pos[0, 0]) == 5.0
    assert float(st2.pos[2, 0]) == 0.0           # masked slot unchanged


def test_rewire_symmetrizes_and_masks():
    st = make_graph_state(4, np.zeros((3, 2)), [], [1, 1, 1], active=3)
    adj = np.zeros((4, 4), np.float32)
    adj[0, 1] = 1.0           # one-directional input
    adj[2, 3] = 1.0           # touches masked vertex 3
    st2 = rewire(st, jnp.asarray(adj))
    assert float(st2.adj[1, 0]) == 1.0
    assert float(st2.adj[2, 3]) == 0.0
    assert float(jnp.diagonal(st2.adj).sum()) == 0.0


def test_perturb_keeps_invariants(rng):
    st = random_scenario(rng, 24, 18, 40)
    for _ in range(5):
        st = perturb_scenario(rng, st, 0.3)
        adj = np.asarray(st.adj)
        mask = np.asarray(st.mask)
        np.testing.assert_allclose(adj, adj.T)
        assert np.all(np.diagonal(adj) == 0)
        # no edges incident to masked vertices
        assert np.all(adj[mask == 0] == 0)
        assert np.all(adj[:, mask == 0] == 0)


# -- property suite: every churn path preserves the layout invariants --------

def _assert_layout_invariants(state: GraphState) -> None:
    """The §3.2 contract every mutation must preserve: symmetric adjacency,
    zero diagonal, no edges or task bits on inactive slots, binary mask,
    and ``num_active`` equal to the mask population."""
    adj = np.asarray(state.adj)
    mask = np.asarray(state.mask)
    np.testing.assert_array_equal(adj, adj.T)
    assert np.all(np.diagonal(adj) == 0)
    assert np.all(adj[mask == 0] == 0)
    assert np.all(adj[:, mask == 0] == 0)
    assert np.all((mask == 0) | (mask == 1))
    assert np.all(np.asarray(state.task_kb)[mask == 0] == 0)
    assert float(state.num_active()) == mask.sum()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([0.1, 0.3, 0.6]))
def test_property_perturb_preserves_invariants(seed, rate):
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, 20, 14, 30)
    for _ in range(3):
        state = perturb_scenario(rng, state, rate)
        _assert_layout_invariants(state)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 12))
def test_property_arrival_wave_counts_and_invariants(seed, count):
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, 16, 9, 20)
    before = int(np.asarray(state.mask).sum())
    grown = arrival_wave(rng, state, count)
    _assert_layout_invariants(grown)
    want = before + min(count, state.capacity - before)
    assert int(np.asarray(grown.mask).sum()) == want
    # arrivals only ever activate — nobody already active is touched
    assert np.all(np.asarray(grown.mask) >= np.asarray(state.mask))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 12))
def test_property_departure_wave_counts_and_invariants(seed, count):
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, 16, 9, 20)
    before = int(np.asarray(state.mask).sum())
    shrunk = departure_wave(rng, state, count)
    _assert_layout_invariants(shrunk)
    assert int(np.asarray(shrunk.mask).sum()) == before - min(count, before)
    assert np.all(np.asarray(shrunk.mask) <= np.asarray(state.mask))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 2**16))
def test_property_add_users_arbitrary_adjacency(seed, adj_seed):
    """``add_users`` must sanitize an *arbitrary* (asymmetric, self-looped,
    mask-violating) proposed adjacency into a legal layout."""
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, 12, 6, 12)
    mask = np.asarray(state.mask)
    add = ((np.random.default_rng(adj_seed).random(12) < 0.5)
           & (mask == 0)).astype(np.float32)
    raw = (np.random.default_rng(adj_seed + 1)
           .random((12, 12)) < 0.4).astype(np.float32)   # deliberately dirty
    grown = add_users(state, jnp.asarray(add),
                      jnp.asarray(rng.uniform(0, 100, (12, 2))
                                  .astype(np.float32)),
                      jnp.asarray(rng.uniform(1, 9, 12).astype(np.float32)),
                      jnp.asarray(raw))
    _assert_layout_invariants(grown)
    assert int(np.asarray(grown.mask).sum()) == int(mask.sum() + add.sum())


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 2**16))
def test_property_remove_users_subset(seed, drop_seed):
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, 12, 8, 16)
    drop = (np.random.default_rng(drop_seed).random(12) < 0.4) \
        .astype(np.float32)
    shrunk = remove_users(state, jnp.asarray(drop))
    _assert_layout_invariants(shrunk)
    gone = (np.asarray(state.mask) > 0) & (drop > 0)
    assert int(np.asarray(shrunk.mask).sum()) == \
        int(np.asarray(state.mask).sum()) - int(gone.sum())


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([EVENT_ARRIVE,
                                                   EVENT_DEPART]),
       st.integers(1, 6))
def test_property_apply_user_event_matches_wave(seed, kind, count):
    """The event dispatcher is exactly the wave helpers (same rng stream ⇒
    bitwise-identical states) — the fault injector's determinism rests on
    this."""
    state = random_scenario(np.random.default_rng(seed), 14, 8, 18)
    via_event = apply_user_event(np.random.default_rng(seed + 1), state,
                                 GraphEvent(0, kind, count=count))
    wave = arrival_wave if kind == EVENT_ARRIVE else departure_wave
    direct = wave(np.random.default_rng(seed + 1), state, count)
    for a, b in zip(via_event, direct):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _assert_layout_invariants(via_event)

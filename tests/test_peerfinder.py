"""PeerFinder discovery + Resource DoS defense over real sockets.

Reference intents covered (SURVEY §2.6):
- bootstrap from ONE seed address into a full mesh via ENDPOINTS gossip
  (peerfinder/impl/PeerSlotLogic.h, Livecache/Bootcache),
- bootcache valence persistence across restarts (Bootcache.h),
- a garbage-flooding peer is charged and disconnected, and stays
  rejected while its balance is above the drop line
  (resource/impl/Logic.h:422-509, PeerImp.cpp:129-131),
- adversarial framing: malformed frames / oversized claims close the
  peer without wedging the overlay (hack-test.js intent).
"""

from __future__ import annotations

import os
import socket
import struct
import time

import pytest

from stellard_tpu.overlay.peerfinder import Bootcache, Livecache, PeerFinder
from stellard_tpu.overlay.resource import (
    Disposition,
    FEE_INVALID_SIGNATURE,
    ResourceManager,
)
from stellard_tpu.overlay.tcp import TcpOverlay
from stellard_tpu.protocol.keys import KeyPair

MASTER = KeyPair.from_passphrase("masterpassphrase")
SPEED = 5.0


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def wait_until(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.1)
    return pred()


def make_overlay(key, unl, port, peer_addrs, ntime, clock, **kw):
    return TcpOverlay(
        key=key,
        unl=unl,
        quorum=3,
        port=port,
        peer_addrs=peer_addrs,
        network_time=ntime,
        clock=clock,
        timer_interval=0.15,
        idle_interval=4,
        gossip_interval=0.3,
        **kw,
    )


class TestUnits:
    def test_bootcache_valence_and_persistence(self, tmp_path):
        path = str(tmp_path / "bootcache.jsonl")
        bc = Bootcache(path)
        bc.insert(("10.0.0.1", 51235))
        bc.insert(("10.0.0.2", 51235))
        for _ in range(3):
            bc.on_success(("10.0.0.2", 51235))
        bc.on_failure(("10.0.0.1", 51235))
        assert bc.ranked()[0] == ("10.0.0.2", 51235)
        bc.save()
        bc2 = Bootcache(path)
        assert len(bc2) == 2
        assert bc2.ranked()[0] == ("10.0.0.2", 51235)

    def test_livecache_hops_and_expiry(self):
        now = [0.0]
        lc = Livecache(clock=lambda: now[0])
        lc.insert(("10.0.0.1", 1), hops=2)
        lc.insert(("10.0.0.1", 1), hops=1)  # lower hop wins
        lc.insert(("10.0.0.2", 2), hops=9)  # over maxHops: discarded
        assert lc.sample() == [("10.0.0.1", 1, 1)]
        now[0] = 31.0
        assert len(lc) == 0

    def test_peerfinder_policy_and_gossip(self):
        now = [0.0]
        pf = PeerFinder(
            fixed=[("127.0.0.1", 1000)], out_desired=3, clock=lambda: now[0]
        )
        pf.on_endpoints(
            [("0.0.0.0", 2000, 0), ("10.1.1.1", 3000, 2), ("bad", 0, 1)],
            sender=("10.9.9.9", 55555),
        )
        # hop-0 host rewritten to the sender's observed address
        assert ("10.9.9.9", 2000) in pf.livecache.addrs()
        targets = pf.dial_targets(set(), set(), 0, 0)
        assert targets[0] == ("127.0.0.1", 1000)  # fixed first
        assert ("10.9.9.9", 2000) in targets
        # failure backoff suppresses redial
        pf.on_failure(("127.0.0.1", 1000))
        assert ("127.0.0.1", 1000) not in pf.dial_targets(set(), set(), 0, 0)
        now[0] = 20.0
        assert ("127.0.0.1", 1000) in pf.dial_targets(set(), set(), 0, 0)
        # gossip: self at hop 0, re-shares at hop+1
        sample = pf.gossip_sample(("0.0.0.0", 1000))
        assert sample[0] == ("0.0.0.0", 1000, 0)
        assert ("10.1.1.1", 3000, 3) in sample

    def test_reconnect_backoff_exponential_with_jitter(self):
        """Consecutive dial failures back an address off exponentially
        (base * 2^(n-1), capped) with deterministic jitter; success
        resets the ladder (ISSUE 9 satellite: no tight reconnect spin
        against a dead address)."""
        now = [0.0]
        pf = PeerFinder(fixed=[("127.0.0.1", 1000)], clock=lambda: now[0])
        addr = ("127.0.0.1", 1000)
        assert pf.backoff_delay(addr) == 0.0
        delays = []
        for _ in range(5):
            pf.on_failure(addr)
            delays.append(pf.backoff_delay(addr))
        # exponential ladder: every rung at least ~1.6x the previous
        # (2x growth, jitter bounded at +25%)
        for a, b in zip(delays, delays[1:]):
            assert b >= a * 1.6
        # jitter present but bounded
        base = pf.backoff_base
        assert base <= delays[0] <= base * 1.25
        # capped
        for _ in range(10):
            pf.on_failure(addr)
        assert pf.backoff_delay(addr) <= pf.backoff_max * 1.25
        # jitter is a pure function: same count, same delay
        assert pf.backoff_delay(addr) == pf.backoff_delay(addr)
        # dial_targets honors the CURRENT rung
        assert addr not in pf.dial_targets(set(), set(), 0, 0)
        now[0] += pf.backoff_max * 1.25 + 1
        assert addr in pf.dial_targets(set(), set(), 0, 0)
        # success resets the ladder
        pf.on_success(addr)
        assert pf.backoff_delay(addr) == 0.0
        pf.on_failure(addr)
        assert pf.backoff_delay(addr) <= pf.backoff_base * 1.25

    def test_refusing_socket_dials_are_backed_off(self):
        """A live overlay dialing an address that refuses connections
        must space its attempts out on the backoff ladder instead of
        redialing every connect-loop tick."""
        # a port that actively refuses: bind+close so nothing listens
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        refused_port = s.getsockname()[1]
        s.close()
        port = free_ports(1)[0]
        key = KeyPair.from_passphrase("backoff-test")
        ov = make_overlay(
            key, set(), port, [("127.0.0.1", refused_port)],
            lambda: 0, time.monotonic,
        )
        # fast ladder so the test observes >1 rung quickly
        ov.peerfinder.backoff_base = 0.4
        attempts = []
        orig = ov.peerfinder.on_failure

        def counting_failure(addr):
            attempts.append(time.monotonic())
            orig(addr)

        ov.peerfinder.on_failure = counting_failure
        ov.start_network()
        try:
            time.sleep(3.0)
        finally:
            ov.stop()
        # a tight spin would rack up dozens of dials in 3s (the dial
        # itself fails in ~1ms on ECONNREFUSED); the ladder allows only
        # a handful, and the gaps must GROW
        assert 1 <= len(attempts) <= 6, attempts
        if len(attempts) >= 3:
            gaps = [b - a for a, b in zip(attempts, attempts[1:])]
            assert gaps[-1] > gaps[0] * 1.5

    def test_resource_decay_and_drop(self):
        now = [0.0]
        rm = ResourceManager(clock=lambda: now[0])
        addr = ("6.6.6.6", 123)
        disp = Disposition.OK
        for _ in range(15):
            disp = rm.charge(addr, FEE_INVALID_SIGNATURE)
        assert disp == Disposition.DROP
        assert not rm.should_admit(addr)
        now[0] = 120.0  # several decay half-lives later
        assert rm.should_admit(addr)
        now[0] = 500.0  # idle past secondsUntilExpiration
        rm.sweep()
        assert rm.get_json()["entries"] == {}


@pytest.fixture()
def seeded_net(tmp_path):
    """4 validators; #1 is the seed, #2-#4 know ONLY the seed address."""
    n = 4
    ports = free_ports(n)
    keys = [KeyPair.from_passphrase(f"pf-val-{i}") for i in range(n)]
    unl = {k.public for k in keys}
    t0 = time.monotonic()
    clock = lambda: (time.monotonic() - t0) * SPEED
    ntime = lambda: 30_000_000 + int(clock())
    overlays = []
    for i in range(n):
        peer_addrs = [] if i == 0 else [("127.0.0.1", ports[0])]
        overlays.append(
            make_overlay(
                keys[i],
                unl,
                ports[i],
                peer_addrs,
                ntime,
                clock,
                bootcache_path=str(tmp_path / f"bootcache{i}.jsonl"),
            )
        )
    for ov in overlays:
        ov.start(MASTER.account_id, close_time=ntime())
    yield overlays, ports
    for ov in overlays:
        ov.stop()


class TestDiscovery:
    def test_bootstrap_from_one_seed(self, seeded_net):
        overlays, ports = seeded_net
        # gossip must grow the net to a full mesh: every node sees all 3
        # others although only the seed was configured anywhere
        assert wait_until(
            lambda: all(ov.peer_count() == 3 for ov in overlays), 30
        ), [ov.peer_count() for ov in overlays]
        # consensus actually runs over the discovered mesh
        assert wait_until(
            lambda: all(ov.node.lm.closed_ledger().seq >= 3 for ov in overlays),
            30,
        )
        # bootcache learned non-seed endpoints (persisted on stop)
        assert all(len(ov.peerfinder.bootcache) >= 3 for ov in overlays)


class TestAbuse:
    def test_garbage_flooder_is_dropped_and_rejected(self, seeded_net):
        overlays, ports = seeded_net
        victim = overlays[0]
        assert wait_until(lambda: victim.peer_count() == 3, 30)

        # flood garbage frames: each connection costs a malformed-request
        # charge (10) and is closed; the balance accumulates per-endpoint
        # until the drop line (1500), after which the admission gate
        # refuses the connection before the handshake
        def flood_once() -> bool:
            """Returns True once the victim refuses us at admission."""
            try:
                s = socket.create_connection(("127.0.0.1", ports[0]), timeout=2)
            except OSError:
                return False
            try:
                s.settimeout(2.0)
                their_nonce = s.recv(32)
                if not their_nonce:
                    return True  # refused before handshake: gate is up
                s.sendall(os.urandom(32))  # our nonce
                junk = struct.pack(">IH", 10, 999) + os.urandom(10)
                for _ in range(50):
                    s.sendall(junk)
                    time.sleep(0.002)
                return False
            except OSError:
                return False  # charged + closed; reconnect and repeat
            finally:
                s.close()

        deadline = time.monotonic() + 60
        refused = False
        while time.monotonic() < deadline:
            if flood_once():
                refused = True
                break
        assert refused, victim.resources.get_json()
        # endpoint is now above the drop threshold: reconnects are refused
        # at accept time (admission gate)
        assert not victim.resources.should_admit(("127.0.0.1", 55555))
        # the legit mesh survived the flood. The count was asserted on
        # the instant and read 2 under a loaded run (1 run in 8 with six
        # workers busy): a crossing-dial resolution can replace one
        # session seconds late there (TestDialChurn settles for the same
        # reason), so it gets the wait this file's other cases have
        assert wait_until(lambda: victim.peer_count() == 3, 30)
        assert wait_until(
            lambda: all(
                ov.node.lm.closed_ledger().seq
                >= overlays[0].node.lm.closed_ledger().seq - 1
                for ov in overlays
            ),
            30,
        )


class TestDialChurn:
    def test_established_sessions_not_churned_by_redial_timer(self, seeded_net):
        """r2 regression guard: the connect loop must never dial over (and
        thereby displace) an established live session."""
        overlays, ports = seeded_net
        assert wait_until(lambda: all(ov.peer_count() == 3 for ov in overlays), 30)
        # snapshot session object identities
        def sessions(ov):
            with ov._peers_lock:
                return {pk: id(p) for pk, p in ov.peers.items()}

        # settle first: right after the count reaches 3, a legitimate
        # crossing-dial resolution can still replace one session (both
        # sides dialed simultaneously; the loser is dropped) — on a
        # loaded box that lands seconds late. Churn-by-REDIAL, the
        # regression under guard, only shows after the graph is quiet.
        before = [sessions(ov) for ov in overlays]
        deadline = time.time() + 30
        while time.time() < deadline:
            time.sleep(2)
            cur = [sessions(ov) for ov in overlays]
            if cur == before:
                break
            before = cur
        time.sleep(5)  # several redial sweeps (sweep period 2s)
        after = [sessions(ov) for ov in overlays]
        assert before == after, "established sessions were churned"


class TestAcquisitionScoring:
    """PeerSet-style selection: ledger-data requests route to the peer
    with the best observed reply rate, with periodic exploration."""

    def test_best_reply_rate_wins(self):
        from types import SimpleNamespace

        from stellard_tpu.overlay.tcp import _acq_score

        good = SimpleNamespace(acq_requests=10, acq_replies=9)
        bad = SimpleNamespace(acq_requests=10, acq_replies=1)
        fresh = SimpleNamespace(acq_requests=0, acq_replies=0)
        ranked = sorted([bad, good, fresh], key=_acq_score)
        # a fresh peer scores optimistically (1/1) so it gets tried
        # before anything with history; a proven-good peer beats a
        # proven-bad one
        assert ranked == [fresh, good, bad]

    def test_outstanding_breaks_ties(self):
        from types import SimpleNamespace

        from stellard_tpu.overlay.tcp import _acq_score

        caught_up = SimpleNamespace(acq_requests=9, acq_replies=9)
        backlogged = SimpleNamespace(acq_requests=19, acq_replies=9)
        # backlogged peer has 10 unanswered requests in flight — the
        # caught-up peer must rank first
        assert _acq_score(caught_up) < _acq_score(backlogged)


# ---------------------------------------------------------------------------
# discrete-event churn simulation (reference: peerfinder/sim/Tests.cpp —
# socket-free, deterministic, virtual clock; VERDICT r3 missing #4)


class _SimNode:
    def __init__(self, i: int, fixed, clock):
        self.addr = (f"10.0.0.{i}", 5000 + i)
        self.alive = True
        self.pf = PeerFinder(
            fixed=fixed, out_desired=3, max_peers=8, clock=clock
        )

    def neighbors(self, edges) -> set:
        out = {b for (a, b) in edges if a == self.addr}
        inn = {a for (a, b) in edges if b == self.addr}
        return out | inn

    def in_count(self, edges) -> int:
        return sum(1 for (a, b) in edges if b == self.addr)

    def out_count(self, edges) -> int:
        return sum(1 for (a, b) in edges if a == self.addr)


class _ChurnSim:
    """N nodes, one seed, random joins/leaves. Each tick: dial according
    to PeerFinder policy (receivers enforce slot caps and hand out
    redirects when full), then gossip over live edges."""

    def __init__(self, n: int, seed: int):
        import random

        self.rng = random.Random(seed)
        self.t = 0.0
        clock = lambda: self.t
        seed_addr = (f"10.0.0.0", 5000)
        self.nodes = {}
        for i in range(n):
            fixed = [] if i == 0 else [seed_addr]
            node = _SimNode(i, fixed, clock)
            self.nodes[node.addr] = node
        self.edges: set[tuple] = set()  # (dialer_addr, receiver_addr)

    def live(self):
        return [n for n in self.nodes.values() if n.alive]

    def tick(self):
        self.t += 1.0
        # drop edges touching dead nodes
        self.edges = {
            (a, b)
            for (a, b) in self.edges
            if self.nodes[a].alive and self.nodes[b].alive
        }
        for node in self.live():
            targets = node.pf.dial_targets(
                connected=node.neighbors(self.edges),
                dialing=set(),
                out_count=node.out_count(self.edges),
                total_count=len(node.neighbors(self.edges)),
            )
            for t in targets:
                recv = self.nodes.get(t)
                if recv is None or not recv.alive:
                    node.pf.on_failure(t)
                    continue
                reserved = node.addr in set(map(tuple, recv.pf.fixed))
                if not recv.pf.can_accept_inbound(
                    recv.in_count(self.edges), reserved
                ):
                    # redirect handout instead of a silent drop
                    sample = recv.pf.handout(exclude={recv.addr})
                    node.pf.on_endpoints(
                        [(h, p, 1) for (h, p) in sample], sender=t
                    )
                    node.pf.on_failure(t)
                    continue
                self.edges.add((node.addr, t))
                node.pf.on_success(t)
        # gossip over live edges, both directions
        for (a, b) in list(self.edges):
            for src, dst in ((a, b), (b, a)):
                sample = self.nodes[src].pf.gossip_sample(src)
                self.nodes[dst].pf.on_endpoints(sample, sender=src)

    def assert_caps(self):
        for node in self.live():
            inn = node.in_count(self.edges)
            # fixed-reserved connections may exceed the cap; count only
            # non-reserved inbound against max_in
            fixed_in = sum(
                1
                for (a, b) in self.edges
                if b == node.addr
                and a in set(map(tuple, node.pf.fixed))
            )
            assert inn - fixed_in <= node.pf.max_in, (
                f"{node.addr} inbound {inn} exceeds cap {node.pf.max_in}"
            )
            assert len(node.neighbors(self.edges)) <= node.pf.max_peers + len(
                node.pf.fixed
            )

    def converged(self) -> bool:
        live = self.live()
        if len(live) <= 1:
            return True
        start = live[0].addr
        seen = {start}
        frontier = [start]
        while frontier:
            cur = frontier.pop()
            for nxt in self.nodes[cur].neighbors(self.edges):
                if nxt not in seen and self.nodes[nxt].alive:
                    seen.add(nxt)
                    frontier.append(nxt)
        return seen == {n.addr for n in live}


class TestChurnSim:
    def test_bootstrap_converges_and_respects_caps(self):
        sim = _ChurnSim(n=24, seed=42)
        for _ in range(40):
            sim.tick()
            sim.assert_caps()
        assert sim.converged(), "bootstrap from one seed must mesh the net"

    def test_reconverges_after_churn(self):
        sim = _ChurnSim(n=24, seed=7)
        for _ in range(30):
            sim.tick()
        # churn phase: random kills and revivals (up to 6 dead at once)
        dead: list = []
        for _ in range(60):
            if sim.rng.random() < 0.3 and len(dead) < 6:
                victim = sim.rng.choice(sim.live()[1:])  # never the seed
                victim.alive = False
                dead.append(victim)
            if sim.rng.random() < 0.2 and dead:
                dead.pop(sim.rng.randrange(len(dead))).alive = True
            sim.tick()
            sim.assert_caps()
        for node in dead:
            node.alive = True
        # recovery: everyone alive again; the mesh must re-form
        for _ in range(80):
            sim.tick()
            sim.assert_caps()
            if sim.converged():
                break
        assert sim.converged(), "net must reconverge after churn"

    def test_full_seed_redirects_connectors(self):
        """When the seed's inbound slots fill, later joiners still mesh
        via handout addresses (the redirect path does real work)."""
        sim = _ChurnSim(n=30, seed=3)
        for _ in range(60):
            sim.tick()
        sim.assert_caps()
        assert sim.converged()
        # the seed must NOT be connected to everyone (slots capped) —
        # proof the mesh grew through redirects/gossip, not a star
        seed = sim.nodes[("10.0.0.0", 5000)]
        assert len(seed.neighbors(sim.edges)) < len(sim.live()) - 1

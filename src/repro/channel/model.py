"""Rayleigh block-fading link simulation (eq. 4).

SNR_{d,t} = P h_{d,t} r_d^-alpha / (W^y N_0),  h ~ Exp(1) IID.
A slot decodes iff SNR >= theta, delivering tau * W^y * log2(1 + theta)
bits.  Latency T^y = first slot where cumulative bits >= payload;
outage if T^y > T_max.

The draw itself lives in :func:`link_outcomes`, which accepts the success
probability and the required slot count as *traced* scalars — the
protocol-sweep engine (repro.sweep) vmaps it over per-config channel
regimes, while the host-side :func:`simulate_link`/:func:`round_trip`
wrappers feed it Python scalars.  Both paths therefore consume the PRNG
identically: equal keys and equal (p, slots) values give bitwise-equal
masks and latencies, which is what the sweep-vs-loop equivalence tests
lock down.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ChannelConfig:
    """Paper Sec. IV defaults."""
    num_devices: int = 10
    num_channels: int = 2          # N_ch
    bandwidth_hz: float = 10e6     # W
    p_up_dbm: float = 23.0
    p_dn_dbm: float = 40.0
    distance_m: float = 1000.0     # r_d
    pathloss_exp: float = 4.0      # alpha
    noise_dbm_hz: float = -174.0   # N_0
    theta: float = 3.0             # target SNR (linear)
    tau_s: float = 1e-3            # slot / coherence time
    t_max_slots: int = 100
    # Straggler model: per-device local compute time ~ Exp(compute_mean_s)
    # drawn each round; a device past deadline_s is dropped from the
    # aggregation set exactly like an uplink outage.  The defaults
    # disable the stage entirely (no draw, no latency term).
    compute_mean_s: float = 0.0
    deadline_s: float = float("inf")

    def link_budget(self, up: bool) -> tuple[float, float]:
        """Returns (success probability per slot, bits per good slot)."""
        w = self.bandwidth_hz * (self.num_channels / self.num_devices
                                 if up else 1.0)
        p_tx = 10 ** (((self.p_up_dbm if up else self.p_dn_dbm) - 30) / 10)
        n0 = 10 ** ((self.noise_dbm_hz - 30) / 10)
        noise = w * n0
        mean_snr = p_tx * self.distance_m ** (-self.pathloss_exp) / noise
        p_success = math.exp(-self.theta / mean_snr)  # P(h >= theta/meanSNR)
        bits = self.tau_s * w * math.log2(1.0 + self.theta)
        return p_success, bits


def slots_needed(payload_bits: float, bits_per_slot: float) -> int:
    """Host-side decode-slot requirement for one payload (>= 1)."""
    return max(1, math.ceil(payload_bits / bits_per_slot))


def link_outcomes(key, p_success, slots, n_links: int, t_max_slots: int):
    """Traced core of the link draw: (latency_slots (n,), success (n,)).

    ``p_success`` and ``slots`` may be Python scalars or traced scalars;
    ``n_links``/``t_max_slots`` are static (they size the bernoulli draw).
    Latency is t_max for outage links (they spent the whole window
    trying), per Sec. II-C.
    """
    good = jax.random.bernoulli(key, p_success, (n_links, t_max_slots))
    cum = jnp.cumsum(good.astype(jnp.int32), axis=1)
    reached = cum >= slots
    latency = jnp.where(reached.any(axis=1),
                        jnp.argmax(reached, axis=1) + 1,
                        t_max_slots)
    return latency, reached.any(axis=1)


def slowest_ok_slots(t, ok, t_max_slots: int):
    """Slots spent waiting on the slowest *successful* link; the full
    window only when every link outages (they contribute nothing)."""
    return jnp.where(jnp.any(ok), jnp.max(jnp.where(ok, t, 0)), t_max_slots)


def compute_outcomes(key, mean_s, deadline_s, n_links: int):
    """Traced per-device compute-time draw for the straggler stage:
    t ~ Exp(mean_s) IID, a device "finishes" iff t <= deadline_s.

    Returns (compute_s (n,), finished (n,) bool).  ``mean_s`` and
    ``deadline_s`` may be traced scalars; ``n_links`` is static.  The
    stage keys off its own fold of the round key, so enabling it never
    perturbs the channel draw stream.
    """
    with jax.named_scope("link_draw"):
        t = mean_s * jax.random.exponential(key, (n_links,))
        return t, t <= deadline_s


def slowest_ok_time(t, ok, deadline_s):
    """Seconds spent waiting on the slowest device that *finished*; the
    full deadline only when every device straggles (the server cannot
    know nobody will report until the deadline passes)."""
    return jnp.where(jnp.any(ok), jnp.max(jnp.where(ok, t, 0.0)),
                     deadline_s)


def simulate_link(key, cfg: ChannelConfig, payload_bits: float, up: bool,
                  n_links: int):
    """Simulate ``n_links`` independent links for one global update.

    Returns (latency_slots (n,), success (n,) bool).
    """
    p, bits = cfg.link_budget(up)
    return link_outcomes(key, p, slots_needed(payload_bits, bits), n_links,
                         cfg.t_max_slots)


def round_trip(key, cfg: ChannelConfig, up_bits: float, dn_bits: float):
    """One global update: per-device uplink (FDMA unicast) + downlink
    (multicast: one transmission, every device must decode it).

    Returns dict with per-device success masks and the round's latency in
    seconds: tau * (max successful T_up + max successful T_dn), as the
    server waits for the slowest *non-outage* device — outage links are
    pinned at t_max_slots and must not inflate the round (they contribute
    nothing to the update).  Only when every link of a direction outages
    does that direction cost the full T_max window.
    """
    ku, kd = jax.random.split(key)
    t_up, ok_up = simulate_link(ku, cfg, up_bits, True, cfg.num_devices)
    t_dn, ok_dn = simulate_link(kd, cfg, dn_bits, False, cfg.num_devices)

    latency_s = cfg.tau_s * (
        float(slowest_ok_slots(t_up, ok_up, cfg.t_max_slots)) +
        float(slowest_ok_slots(t_dn, ok_dn, cfg.t_max_slots)))
    return {"up_ok": ok_up, "dn_ok": ok_dn, "t_up": t_up, "t_dn": t_dn,
            "latency_s": latency_s}


def round_trip_traced(key, p_up, up_slots, p_dn, dn_slots, n_links: int,
                      t_max_slots: int, tau_s: float):
    """Fully-traced :func:`round_trip` for the protocol-sweep engine.

    ``p_up``/``p_dn`` (per-slot success probabilities) and
    ``up_slots``/``dn_slots`` (decode-slot requirements, precomputed
    host-side with :func:`slots_needed` so no traced-float ceil can drift
    from the loop path) may be per-config traced scalars; vmapping this
    function over them batches whole channel regimes into one draw.
    Given equal inputs it consumes the PRNG exactly like ``round_trip``.
    """
    with jax.named_scope("link_draw"):
        ku, kd = jax.random.split(key)
        t_up, ok_up = link_outcomes(ku, p_up, up_slots, n_links,
                                    t_max_slots)
        t_dn, ok_dn = link_outcomes(kd, p_dn, dn_slots, n_links,
                                    t_max_slots)
        latency_s = tau_s * (slowest_ok_slots(t_up, ok_up, t_max_slots) +
                             slowest_ok_slots(t_dn, ok_dn, t_max_slots))
    return {"up_ok": ok_up, "dn_ok": ok_dn, "t_up": t_up, "t_dn": t_dn,
            "latency_s": latency_s}

"""The plain reference round that decides ``correct``.

A straightforward ``jax.numpy`` rewrite of one federated round of the
paper (Algorithm 1), written from the paper and the configuration file,
importing nothing of the program under test:

* the 3-layer CNN (2 conv + 1 FC) and its seeded initialisation;
* local SGD (eqs. 1 and 3): cross-entropy plus ``beta`` times the
  distillation term against the device's copy of G_out, and the
  per-label average outputs the device uploads;
* the Rayleigh block-fading link draw (eq. 4) with its decode-slot
  requirement, and the straggler deadline;
* eq. 2, the per-label output average over the devices whose uplink
  decoded;
* eq. 5, the server's output-to-model conversion (FLD family);
* the downlink, gated per device, and the test accuracy of the
  evaluated device;
* seeded cohort sampling over a device pool.

It follows the program's seeding conventions (which key feeds which
draw), so that, given the same seed, both draw the same batches and the
same link outcomes.  ``dtype=float32`` runs every matrix product at
``Precision.HIGHEST``; ``dtype=bfloat16`` is the control: parameters,
inputs and activations in bfloat16, label counts in float32.

The one input it takes from the program is the server's round-1 seed
set (the Mix2FLD inversely mixed-up samples), which the host prepares
once per job before the first round.  That set is checked on its own
(``checks.seed_numbers``) against the uploads the reference rebuilds
here (eq. 6, ``uploads``): every seed sample, mixed forward again with
its group, has to give back an upload with the sample's labels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

B_ELEM = 32  # bits per transmitted weight or output element


class Reference:
    """One cell's reference: ``config`` and ``traffic`` are the cell's
    files; ``fault`` plants a known fault (``"half_batch"``: local SGD
    takes the loss over the first half of each batch)."""

    def __init__(self, config: dict, traffic: dict, dtype=jnp.float32,
                 fault: str | None = None):
        self.cfg = config
        self.traffic = traffic
        self.dtype = jnp.dtype(dtype)
        self.prec = (jax.lax.Precision.HIGHEST
                     if self.dtype == jnp.float32 else None)
        self.fault = fault
        self.C = int(config["num_classes"])
        self.shape = tuple(int(s) for s in config["input_shape"])
        self.protocol = traffic["protocol"]
        self._local = jax.jit(jax.vmap(
            self._local_train, in_axes=(0, 0, 0, 0, 0, None, None)))
        self._convert = jax.jit(self._conversion)
        self._acc = jax.jit(self._accuracy)

    # -- the model ------------------------------------------------------
    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        c1, c2 = self.cfg["conv_channels"]
        k, cin = int(self.cfg["kernel"]), self.shape[2]
        pool = int(self.cfg["pool"])
        fc_in = (self.shape[0] // pool // pool) * \
            (self.shape[1] // pool // pool) * c2
        f32 = jnp.float32
        return {
            "conv1": {"w": jax.random.normal(k1, (k, k, cin, c1), f32) *
                      (1.0 / jnp.sqrt(k * k * cin)),
                      "b": jnp.zeros((c1,), f32)},
            "conv2": {"w": jax.random.normal(k2, (k, k, c1, c2), f32) *
                      (1.0 / jnp.sqrt(k * k * c1)),
                      "b": jnp.zeros((c2,), f32)},
            "fc": {"w": jax.random.normal(k3, (fc_in, self.C), f32) /
                   jnp.sqrt(fc_in),
                   "b": jnp.zeros((self.C,), f32)},
        }

    def apply(self, params, x):
        pool = int(self.cfg["pool"])

        def conv(h, p):
            y = jax.lax.conv_general_dilated(
                h, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=self.prec)
            return y + p["b"]

        def maxpool(h):
            return jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, pool, pool, 1),
                (1, pool, pool, 1), "VALID")

        h = maxpool(jax.nn.relu(conv(x.astype(self.dtype), params["conv1"])))
        h = maxpool(jax.nn.relu(conv(h, params["conv2"])))
        h = h.reshape(h.shape[0], -1)
        return jnp.dot(h, params["fc"]["w"], precision=self.prec) + \
            params["fc"]["b"]

    def _loss(self, params, xb, yb, gout, beta):
        """phi + beta * psi: cross-entropy against the labels (integer,
        or soft rows where seed prep fell back to Mixup's soft labels)
        and against the G_out row of each sample's (arg-max) label."""
        logits = self.apply(params, xb)
        logp = jax.nn.log_softmax(logits, axis=-1)
        if yb.ndim == 1:
            phi = -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))
            row = yb
        else:
            phi = -jnp.mean(jnp.sum(yb.astype(logp.dtype) * logp, -1))
            row = jnp.argmax(yb, axis=-1)
        psi = -jnp.mean(jnp.sum(gout[row].astype(logp.dtype) * logp, -1))
        return phi + beta * psi, logits

    def cast(self, tree):
        return jax.tree.map(lambda a: a.astype(self.dtype), tree)

    # -- device side ----------------------------------------------------
    def _local_train(self, params, x, y, key, gout, use_kd, eta):
        K = int(self.cfg["local_iters"])
        B = int(self.cfg["local_batch"])
        beta = jnp.where(use_kd, float(self.cfg["beta"]), 0.0)
        n = x.shape[0]

        def step(carry, k):
            p, out_sum, cnt = carry
            idx = jax.random.randint(k, (B,), 0, n)
            xb, yb = x[idx], y[idx]
            if self.fault == "half_batch":
                xb, yb = xb[:B // 2], yb[:B // 2]
            (l, logits), g = jax.value_and_grad(self._loss, has_aux=True)(
                p, xb, yb, gout, beta)
            p = jax.tree.map(lambda a, b: (a - eta * b).astype(a.dtype),
                             p, g)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            oh = jax.nn.one_hot(yb, self.C, dtype=jnp.float32)
            out_sum = out_sum + jnp.dot(oh.T, probs,
                                        precision=jax.lax.Precision.HIGHEST)
            return (p, out_sum, cnt + jnp.sum(oh, 0)), l.astype(jnp.float32)

        init = (params, jnp.zeros((self.C, self.C)), jnp.zeros((self.C,)))
        (params, out_sum, cnt), losses = jax.lax.scan(
            step, init, jax.random.split(key, K))
        favg = out_sum / jnp.maximum(cnt[:, None], 1.0)
        return params, favg, cnt, jnp.mean(losses)

    # -- server side ----------------------------------------------------
    @staticmethod
    def aggregate(favg, cnt, ok):
        """eq. 2 over the devices whose uplink decoded."""
        cw = ok[:, None] * cnt
        num = jnp.einsum("dc,dcm->cm", cw, favg,
                         precision=jax.lax.Precision.HIGHEST)
        den = jnp.sum(cw, axis=0)
        return num / jnp.maximum(den[:, None], 1.0)

    def _conversion(self, params, sx, sy, gout, key, eta):
        """eq. 5: K_s SGD steps over the seed set with the KD target row
        of each sample's (hard) label."""
        K = int(self.cfg["server_iters"])
        B = int(self.cfg["server_batch"])
        beta = float(self.cfg["beta"])
        n = sx.shape[0]

        def step(p, k):
            idx = jax.random.randint(k, (B,), 0, n)
            (l, _), g = jax.value_and_grad(self._loss, has_aux=True)(
                p, sx[idx], sy[idx], gout, beta)
            return jax.tree.map(lambda a, b: (a - eta * b).astype(a.dtype),
                                p, g), l

        params, _ = jax.lax.scan(step, params, jax.random.split(key, K))
        return params

    def _accuracy(self, params, x, y):
        pred = jnp.argmax(self.apply(params, x), axis=-1)
        return jnp.mean((pred == y).astype(jnp.float32))

    # -- the link (eq. 4) -----------------------------------------------
    def link_budget(self, up: bool, p_up_dbm: float):
        """(success probability of one slot, bits one good slot
        carries): the uplink band is split over the devices on air."""
        ch = self.cfg["channel"]
        w = ch["bandwidth_hz"] * (ch["num_channels"] / ch["num_devices"]
                                  if up else 1.0)
        p_tx = 10 ** (((p_up_dbm if up else ch["p_dn_dbm"]) - 30) / 10)
        noise = w * 10 ** ((ch["noise_dbm_hz"] - 30) / 10)
        mean_snr = p_tx * ch["distance_m"] ** -ch["pathloss_exp"] / noise
        return (math.exp(-ch["theta"] / mean_snr),
                ch["tau_s"] * w * math.log2(1 + ch["theta"]))

    def slots(self, first_round: bool, p_up_dbm: float):
        """Decode slots needed (uplink, downlink) for one round's
        payloads: G_out for the FD family, the model on the FLD
        downlink, the N_S seed samples riding on the FLD round-1
        uplink."""
        C = self.C
        out_bits = B_ELEM * C * C
        up_bits = dn_bits = out_bits
        if self.protocol != "fd":
            dn_bits = B_ELEM * int(self.cfg["n_params"])
            if first_round:
                up_bits += 8 * math.prod(self.shape) * int(self.cfg["n_seed"])
        _, bits_up = self.link_budget(True, p_up_dbm)
        _, bits_dn = self.link_budget(False, p_up_dbm)
        return (max(1, math.ceil(up_bits / bits_up)),
                max(1, math.ceil(dn_bits / bits_dn)))

    def link(self, key, n: int, first_round: bool, p_up_dbm: float):
        t_max = int(self.cfg["channel"]["t_max_slots"])

        def draw(k, p, slots):
            good = jax.random.bernoulli(k, p, (n, t_max))
            cum = jnp.cumsum(good.astype(jnp.int32), axis=1)
            return np.asarray((cum >= slots).any(axis=1))

        up_slots, dn_slots = self.slots(first_round, p_up_dbm)
        ku, kd = jax.random.split(key)
        up_ok = draw(ku, self.link_budget(True, p_up_dbm)[0], up_slots)
        dn_ok = draw(kd, self.link_budget(False, p_up_dbm)[0], dn_slots)
        mean_s = float(self.traffic.get("compute_mean_s") or 0.0)
        if mean_s > 0.0:
            t = mean_s * jax.random.exponential(jax.random.fold_in(key, 7),
                                                (n,))
            up_ok = up_ok & np.asarray(t <= float(self.traffic["deadline_s"]))
        return up_ok, dn_ok

    # -- round-1 uploads (eq. 6) ----------------------------------------
    def uploads(self, seed: int, dev_x, dev_y) -> dict:
        """Every device's ``n_seed`` Mixup samples of round 1, as the
        devices upload them (flattened), with their minor (weight lam)
        and major labels: each mixes a uniform local sample with a
        uniform local sample of a uniform other class."""
        lam = float(self.cfg["lam"])
        ns, C = int(self.cfg["n_seed"]), self.C
        _, key = jax.random.split(jax.random.PRNGKey(seed))
        ks = jax.random.fold_in(jax.random.fold_in(key, 1), 2)
        D, n = dev_y.shape

        def one(k, x, y):
            k1, k2, k3 = jax.random.split(k, 3)
            i = jax.random.randint(k1, (ns,), 0, n)
            other = (y[i] + jax.random.randint(k2, (ns,), 1, C)) % C
            g = jax.random.gumbel(k3, (ns, n))
            j = jnp.argmax(jnp.where(y[None] == other[:, None], g,
                                     -jnp.inf), axis=1)
            xi, xj = x[i].astype(self.dtype), x[j].astype(self.dtype)
            return (lam * xi + (1.0 - lam) * xj).reshape(ns, -1), y[i], y[j]

        x, minor, major = jax.jit(jax.vmap(one))(
            jax.random.split(ks, D), dev_x, dev_y)
        return {"x": np.asarray(x.astype(jnp.float32), np.float64
                                ).reshape(D * ns, -1),
                "minor": np.asarray(minor).ravel(),
                "major": np.asarray(major).ravel(),
                "lam": lam, "want": int(self.cfg["n_inverse"]) * D}

    # -- cohort sampling ------------------------------------------------
    def cohort(self, seed: int, round_: int, pool: int):
        """The round's cohort: the ``cohort`` pool devices with the
        smallest uniforms of the round's stream, in index order."""
        size = int(self.traffic.get("cohort") or pool)
        u = np.random.default_rng([seed, 0, round_, 0]).random(pool)
        if size >= pool:
            return None
        return np.sort(np.argsort(u, kind="stable")[:size])

    # -- whole rounds ---------------------------------------------------
    def replay(self, seed: int, data, rounds: int, seeds=None,
               eta: float | None = None, p_up_dbm: float | None = None):
        """Run ``rounds`` rounds from the seeded initial state; returns
        the readings the check compares (see ``checks.compare``)."""
        dev_x, dev_y, test_x, test_y = data
        eta = float(self.cfg["eta"] if eta is None else eta)
        p_up = float(self.cfg["channel"]["p_up_dbm"] if p_up_dbm is None
                     else p_up_dbm)
        C, P = self.C, dev_x.shape[0]
        kinit, key = jax.random.split(jax.random.PRNGKey(seed))
        g0 = self.init(kinit)
        g = self.cast(g0)
        pool = jax.tree.map(lambda a: jnp.broadcast_to(a, (P,) + a.shape), g)
        gout = jnp.full((C, C), 1.0 / C)
        dev_gout = jnp.full((P, C, C), 1.0 / C)
        fd = self.protocol == "fd"
        if not fd:
            sx = jnp.asarray(seeds["train_x"]).astype(self.dtype)
            sy = jnp.asarray(seeds["train_y"])
        rec = {"loss": [], "acc": [], "uplinks": []}
        for p in range(1, rounds + 1):
            kr = jax.random.fold_in(key, p)
            cohort = self.cohort(seed, p, P)
            if cohort is None:
                dp, dg, dx, dy = pool, dev_gout, dev_x, dev_y
            else:
                j = jnp.asarray(cohort)
                dp = jax.tree.map(lambda a: a[j], pool)
                dg, dx, dy = dev_gout[j], dev_x[j], dev_y[j]
            D = dx.shape[0]
            dkeys = jax.random.split(jax.random.fold_in(kr, 1), D)
            dp, favg, cnt, mloss = self._local(dp, dx, dy, dkeys, dg,
                                               p > 1, eta)
            up_ok, dn_ok = self.link(jax.random.fold_in(kr, 3), D, p == 1,
                                     p_up)
            if up_ok.any():
                gout = self.aggregate(favg, cnt, jnp.asarray(up_ok,
                                                             jnp.float32))
            if not fd:
                g = self._convert(g, sx, sy, gout, jax.random.fold_in(kr, 4),
                                  eta)
            mask = jnp.asarray(dn_ok)
            dg = jnp.where(mask[:, None, None], gout[None], dg)
            if not fd:
                dp = jax.tree.map(
                    lambda d, a: jnp.where(
                        mask.reshape((-1,) + (1,) * a.ndim), a[None], d),
                    dp, g)
            if cohort is None:
                pool, dev_gout = dp, dg
                ref_dev = 0
            else:
                pool = jax.tree.map(lambda a, c: a.at[j].set(c), pool, dp)
                dev_gout = dev_gout.at[j].set(dg)
                ref_dev = int(cohort[0])
            ev = jax.tree.map(lambda a: a[ref_dev], pool)
            rec["loss"].append(float(mloss.mean()))
            rec["acc"].append(float(self._acc(ev, test_x, test_y)))
            rec["uplinks"].append(int(up_ok.sum()))
            state = (jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                                  pool, jax.tree.map(
                                      lambda a: a[None], g0))
                     if fd else
                     jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                                  g, g0))
            norms = leaf_norms(state)
            if p == 1:
                rec["update_norms"] = norms
            rec["change_norms"] = norms
        rec["gout"] = np.asarray(gout, np.float64)
        return rec


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf, keyed ``"conv1.w"`` and so on."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = ".".join(str(getattr(k, "key", k)) for k in path)
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32)))))
    return out

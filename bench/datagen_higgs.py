"""A seeded table of the UCI HIGGS shape (Baldi, Sadowski & Whiteson 2014).

The records are not available offline, so the ``higgs`` configuration's
``dataset`` block gives the published shape (28 dense features: 21
low-level kinematics, then 7 derived invariant masses; about 53 % signal;
no missing values) and the generator below fills it column by column, in
the published order:

* lepton pT, eta, phi; missing-energy magnitude, phi; then for each of
  four jets pT, eta, phi, b-tag;
* m_jj, m_jjj, m_lv, m_jlv, m_bb, m_wbb, m_wwbb.

Transverse momenta, the missing-energy magnitude and the masses are
positive and heavy-tailed (log-normal); pseudorapidities are bounded at
+-2.5 (clipped normals, so the edge values repeat); azimuths are uniform
on (-pi, pi); each b-tag takes one of three values, so 256 quantile bins
meet long runs of repeated edges.  The masses share a common factor with
the jets' momenta.  The label is the top 53 % of a logit carried mostly by
the masses (a peak in m_bb and m_wwbb, a slope in m_jjj), with pairwise
interactions, a small low-level part and noise.  The same seed gives the
same table.
"""

from __future__ import annotations

import numpy as np

from bench.datagen import Table

ETA_MAX = 2.5
BTAG_VALUES = (0.0, 1.107, 2.214)
BTAG_SHARES = (0.55, 0.2, 0.25)


def higgs_table(spec: dict, seed: int) -> Table:
    """The table a configuration's ``dataset`` block describes."""
    rng = np.random.default_rng(seed)
    n = int(spec["rows"])
    low, high = int(spec["low_level"]), int(spec["high_level"])
    if (low, high) != (21, 7) or int(spec["features"]) != 28:
        raise ValueError("the HIGGS column model is 21 low-level + 7 masses")

    scale = rng.normal(size=n)  # event hardness, shared by jets and masses

    def pt(sigma):
        return np.exp(0.25 * scale + rng.normal(scale=sigma, size=n))

    def eta():
        return np.clip(rng.normal(scale=1.1, size=n), -ETA_MAX, ETA_MAX)

    def phi():
        return rng.uniform(-np.pi, np.pi, size=n)

    cols = [pt(0.5), eta(), phi(), pt(0.6), phi()]
    for _ in range(4):
        cols += [pt(0.55), eta(), phi(),
                 rng.choice(BTAG_VALUES, size=n, p=BTAG_SHARES)]
    mass = [np.exp(0.2 * scale + rng.normal(scale=0.35, size=n))
            for _ in range(high)]
    x = np.stack(cols + mass, axis=1)

    z = np.log(np.stack(mass, axis=1))
    z = (z - z.mean(axis=0)) / z.std(axis=0)
    m_jj, m_jjj, m_lv, m_jlv, m_bb, m_wbb, m_wwbb = z.T
    btag = x[:, [8, 12, 16, 20]].sum(axis=1) / BTAG_VALUES[-1]
    logit = (1.2 - 0.9 * m_bb ** 2 - 0.7 * (m_wwbb - 0.3) ** 2
             + 0.6 * m_jjj + 0.3 * m_wbb - 0.2 * m_jlv
             + 0.5 * m_jj * m_lv + 0.4 * m_bb * m_wbb
             + 0.15 * btag + 0.1 * np.log(x[:, 0]) * np.abs(x[:, 1])
             + rng.normal(scale=0.8, size=n))
    thresh = np.sort(logit)[int((1.0 - float(spec["positive_rate"])) * n)]
    y = (logit > thresh).astype(np.float32)
    x = x.astype(np.float32)

    perm = rng.permutation(n)
    k_train = int(float(spec["train_share"]) * n)
    tr, te = perm[:k_train], perm[k_train:]
    return Table(x[tr], y[tr], x[te], y[te])

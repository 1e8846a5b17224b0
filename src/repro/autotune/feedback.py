"""Online feedback: per-engine cost corrections from measured sweep times.

Offline calibration (calibrate.py) fixes the *profile*; this module
closes the loop at run time.  Each chunk of HyTM iterations yields one
noisy linear observation

    measured_chunk_seconds ~= sum_e  c_e * modeled_e

where ``modeled_e`` is the modeled time the plans attributed to engine
``e`` over the chunk's iterations.  :class:`OnlineCalibrator` maintains the
exponentially-forgotten normal equations of that regression (EWMA
recursive least squares) and solves for the correction vector ``c``.

Because absolute wall time on the measuring host need not match the
modeled link's units (CPU oracles vs modeled PCIe seconds), the solved
vector is normalized to geometric-mean 1 over the engines that have
actually been observed: only the *relative* corrections matter to
Algorithm 1, which compares engines against each other.  Engines with no
accumulated evidence stay at 1.0.

The correction multiplies the per-engine selection costs
(``cost_model.apply_correction``) inside each iteration's selection, steers the
sharded path's ICI-level exchange choice (``graph_shard.ici_level_cost``),
and persists across queries in ``stream.service.GraphService`` so lane
scheduling keeps learning over a service's lifetime.
"""

from __future__ import annotations

import time

import numpy as np

N_ENGINES = 3  # FILTER, COMPACT, ZEROCOPY


class OnlineCalibrator:
    """EWMA recursive least squares for per-engine correction factors."""

    def __init__(self, decay: float = 0.25, ridge: float = 0.05,
                 clip: tuple[float, float] = (0.05, 20.0), obs=None):
        assert 0.0 < decay <= 1.0, decay
        self.decay = decay
        self.ridge = ridge
        self.clip = clip
        self._A = np.zeros((N_ENGINES, N_ENGINES))
        self._b = np.zeros(N_ENGINES)
        self.n_updates = 0
        # optional repro.obs.TraceRecorder: each folded observation emits
        # one correction-update event (host-side; obs=None records nothing
        # and skips even the correction re-solve)
        self.obs = obs

    def update(self, modeled: np.ndarray, measured_seconds: float) -> None:
        """Fold in one iteration: (3,) modeled per-engine seconds + the
        measured wall time of that iteration.  Each sample is normalized
        by its modeled magnitude so iterations contribute comparable
        weight regardless of frontier size."""
        t = np.asarray(modeled, dtype=float).reshape(-1)
        if t.shape != (N_ENGINES,):
            raise ValueError(f"expected ({N_ENGINES},) modeled times, got {t.shape}")
        norm = float(np.linalg.norm(t))
        if not np.isfinite(measured_seconds) or measured_seconds <= 0 or norm <= 0:
            return
        u = t / norm
        f = 1.0 - self.decay
        self._A = f * self._A + np.outer(u, u)
        self._b = f * self._b + u * (measured_seconds / norm)
        self.n_updates += 1
        if self.obs is not None:
            c = self.correction()
            m = self.obs.metrics
            m.counter("autotune.updates", "calibrator observations").inc(1)
            for e, name in enumerate(("filter", "compact", "zerocopy")):
                m.gauge("autotune.correction",
                        "per-engine cost correction").set(float(c[e]),
                                                          engine=name)
            self.obs.instant(
                "correction_update", cat="autotune", track="autotune",
                vt=float(self.n_updates), measured_seconds=float(measured_seconds),
                modeled=[float(x) for x in t], correction=[float(x) for x in c],
            )

    def observed(self) -> np.ndarray:
        """(3,) bool — engines with accumulated evidence."""
        return np.diag(self._A) > 1e-9

    def correction(self) -> np.ndarray:
        """(3,) multiplicative per-engine correction, geo-mean-1 over the
        observed engines; all-ones until the first update."""
        if self.n_updates == 0:
            return np.ones(N_ENGINES)
        # ridge prior toward the uncorrected model at the data's own
        # scale: s from the one-factor fit measured ≈ s · modeled, so the
        # relative corrections do not depend on the wall-clock units
        ones = np.ones(N_ENGINES)
        weight = float(ones @ self._A @ ones)
        s = float(ones @ self._b) / weight if weight > 0 else 1.0
        if not s > 0:
            s = 1.0
        A = self._A + self.ridge * np.eye(N_ENGINES)
        b = self._b + self.ridge * s * ones
        try:
            c = np.linalg.solve(A, b)
        except np.linalg.LinAlgError:
            return np.ones(N_ENGINES)
        c = np.clip(c, 1e-6, None)
        obs = self.observed()
        if obs.any():
            gm = float(np.exp(np.mean(np.log(c[obs]))))
            if gm > 0:
                c = c / gm
        c = np.where(obs, np.clip(c, *self.clip), 1.0)
        return c.astype(float)

    def observe(self, sync_ref, per_engine_modeled, t_start: float,
                skip: bool = False):
        """The per-chunk wiring shared by ``run_hytm`` (one device and the
        mesh) and ``GraphService``: block on ``sync_ref`` (so the elapsed
        wall time covers the whole chunk), fold the measurement against
        the (3,) per-engine modeled seconds summed over the chunk's
        executed iterations — unless ``skip``, for chunks whose dispatch
        compiled (wall time measures XLA, not the sweep) — and return the
        refreshed correction as a (3,) float32 jax array ready to feed
        the next chunk.  Summing iterations only aggregates observations
        of a model linear in the per-engine regressors, so a chunk
        identifies the same correction vector as its iterations would."""
        import jax
        import jax.numpy as jnp

        jax.block_until_ready(sync_ref)
        if not skip:
            self.update(
                np.asarray(per_engine_modeled, dtype=float),
                time.monotonic() - t_start,
            )
        return jnp.asarray(self.correction(), jnp.float32)

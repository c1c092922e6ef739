"""Host-side shape bookkeeping shared by the uniform (`sim.Simulation`)
and adaptive (`amr.AMRSim`) drivers: CoM/inertia sync after
rasterization, the deforming-body dt cap, and force-diagnostic logging.
The device kernels differ by storage layout; these pieces are layout-free
and must stay identical between the two paths."""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from .ops.forces import FORCE_KEYS


class ShapeHostMixin:
    """Requires: self.shapes, self.time, self.force_log."""

    def _sync_shape_scalars(self, obs):
        """CoM correction + M/J/d_gm bookkeeping (main.cpp:4480-4541).
        One batched device_get — separate np.asarray pulls each pay the
        full device->host latency and a sync of their own."""
        self._sync_shape_scalars_np(*jax.device_get(
            (obs.com, obs.mass, obs.inertia)))

    def _sync_shape_scalars_np(self, com, mass, inertia):
        """Same, from already-fetched host arrays (fused-step path)."""
        com = np.asarray(com, dtype=np.float64)
        mass = np.asarray(mass, dtype=np.float64)
        inertia = np.asarray(inertia, dtype=np.float64)
        for k, s in enumerate(self.shapes):
            s.com[:] = com[k]
            s.M = float(mass[k])
            s.J = float(inertia[k])
            dc = s.center - s.com
            cth, sth = np.cos(s.orientation), np.sin(s.orientation)
            s.d_gm[0] = dc[0] * cth + dc[1] * sth
            s.d_gm[1] = -dc[0] * sth + dc[1] * cth

    def _bodies_record(self) -> Optional[list]:
        """The bodies as the step's record carries them (telemetry
        schema v13): one entry a shape, host floats the step already
        holds after its one pull — the rigid state the momentum solve
        returned and the chi-corrected mass, centre and inertia the
        rasterisation returned; None where the run has no shape. No
        device value is touched."""
        if not self.shapes:
            return None
        return [{"com": [float(s.com[0]), float(s.com[1])],
                 "angle": float(s.orientation),
                 "u": float(s.u), "v": float(s.v),
                 "omega": float(s.omega),
                 "mass": float(s.M), "inertia": float(s.J)}
                for s in self.shapes]

    def _kinematic_dt_cap(self) -> float:
        """Deforming bodies need dt well under their gait period: the
        grid-umax CFL (main.cpp:6579-6595) cannot see the midline's
        future motion when the flow is still quiescent (the curvature
        scheduler ramps from zero), and on coarse grids the diffusive dt
        limit 0.25 h^2/nu can exceed the period itself — advancing the
        kinematics by O(period) per step is meaningless and blows up the
        penalization. The reference dodges this only by always running
        fine grids (h <= 1/1024 keeps the diffusive cap small). 1/20th
        of the fastest period resolves the gait; obstacle-free and
        rigid-shape runs are uncapped, exactly like the reference."""
        periods = [float(s.current_period) for s in self.shapes
                   if getattr(s, "current_period", 0.0) > 0.0]
        return 0.05 * min(periods) if periods else float("inf")

    @staticmethod
    def force_log_header() -> str:
        return ",".join(["time", "shape"] + list(FORCE_KEYS))

    def _record_forces(self, results):
        """Store the 19 diagnostics on each shape + append CSV rows.
        device_get fetches all S x 19 device scalars in one transfer —
        per-scalar float() pulls cost S x 19 round trips."""
        results = jax.device_get(results)
        for k, (s, r) in enumerate(zip(self.shapes, results)):
            s.forces = {key: float(r[key]) for key in FORCE_KEYS}
            if self.force_log is not None:
                row = [f"{self.time:.8g}", str(k)] + [
                    f"{s.forces[key]:.8g}" for key in FORCE_KEYS]
                self.force_log.write(",".join(row) + "\n")

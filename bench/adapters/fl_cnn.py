"""Adapter for the paper-CNN configuration: builds the program's fused
SP-FL scan segment (``FLSimulator._fused_round_body`` scanned from
``FLSimulator._fused_init_carry``, exactly what ``FLSimulator._run_fused``
dispatches under ``round_fusion='scan'``) from the benchmark's own data and
weights, and runs the plain reference over the same inputs."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import checks
from bench.harness import traffic as gen
from bench.reference import fl_cnn as ref
from repro.configs.base import FLConfig
from repro.core import allocation_jax
from repro.obs import ringbuf as obs_ring
from repro.training.fl_loop import FLSimulator

# faults a test or a reading run may plant underneath the timed path
FAULTS = ('state_unchanged', 'half_batch', 'altered_answer')
# held-out images the simulator is built with; the segment never reads them
TEST_IMAGES = 16


def program_seed(seed: int) -> int:
    """The driver's seeds may pass 32 bits; PRNG keys take 32."""
    return int(seed) % (2 ** 32)


def client_order(seed: int, k: int) -> np.ndarray:
    """The run's order of the deployment's clients.  Every seed serves the
    same K clients (data, weights, placement from the traffic file's
    `base_seed`) in another order: the eq. (28) solve's loop counts, and
    so the work of a round, depend on the clients, not on their order."""
    return np.asarray(jax.random.permutation(
        jax.random.PRNGKey(program_seed(seed)), k))


class Cell:
    """One configuration under one traffic mix, built from one seed."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 fault: str | None = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f'unknown fault {fault!r}')
        self.config, self.traffic, self.fault = config, traffic, fault
        self.seed = int(traffic['base_seed'])
        self.order = client_order(seed, int(traffic['clients']))
        self.seg_len = int(traffic['segment_rounds'])
        self.k = int(traffic['clients'])
        self.wire = traffic['wire']
        op = config['fl']
        self.fl = FLConfig(
            n_devices=self.k, quant_bits=op['quant_bits'],
            b0_bits=op['b0_bits'], bandwidth_hz=op['bandwidth_hz'],
            tx_power_dbm=op['tx_power_dbm'],
            noise_psd_dbm=op['noise_psd_dbm'],
            path_loss_exp=op['path_loss_exp'],
            cell_radius_m=op['cell_radius_m'], latency_s=op['latency_s'],
            learning_rate=op['learning_rate'], allocator=op['allocator'],
            allocation_max_iters=op['allocation_max_iters'],
            allocation_tol=op['allocation_tol'],
            compensation='last_global', transport='spfl',
            wire=traffic['wire'], channel=traffic['channel'],
            allocation_backend='jax', allocation_cadence='per_round',
            round_fusion='scan', scan_segment_rounds=self.seg_len,
            telemetry_flush_every=self.seg_len, seed=self.seed)

    # -- inputs made by the benchmark --------------------------------------
    def inputs(self):
        xs, ys = gen.client_images(self.seed, self.traffic)
        return xs[self.order], ys[self.order], self.weights()

    def weights(self):
        return jax.jit(ref.init_params)(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), 0x1417))

    # -- the program --------------------------------------------------------
    def build(self):
        """Make the inputs, build the simulator and compile the segment."""
        xs, ys, params = self.inputs()
        sim = FLSimulator(self.fl, xs, ys, xs[0, :TEST_IMAGES],
                          ys[0, :TEST_IMAGES], seed=self.seed)
        sim.params = params
        sim.gains = sim.gains[self.order]
        self._plant(sim)
        body = sim._fused_round_body()
        carry = sim._fused_init_carry(self.seg_len)
        carry = carry[:3] + (carry[3][self.order],) + carry[4:]
        self.sim = sim
        solve = allocation_jax.solve_traceable
        try:
            if self.fault == 'altered_answer':
                allocation_jax.solve_traceable = _rolled(solve)
            self.compiled = jax.jit(
                lambda c, ns: jax.lax.scan(body, c, ns)).lower(
                    carry, self._ns(0)).compile()
        finally:
            allocation_jax.solve_traceable = solve
        return carry

    def _plant(self, sim):
        if self.fault == 'state_unchanged':
            sim._apply_update = lambda params, ghat: params
        elif self.fault == 'half_batch':
            grads_fn, half = sim._per_client_grads, sim.client_x.shape[1] // 2
            sim._per_client_grads = lambda p, xs, ys: grads_fn(
                p, xs[:, :half], ys[:, :half])

    def _ns(self, segment: int):
        start = segment * self.seg_len
        return jnp.asarray(np.arange(start, start + self.seg_len,
                                     dtype=np.uint32))

    def dispatch(self, carry, index: int):
        """One dispatch of the compiled segment -> (carry, losses)."""
        return self.compiled(carry, self._ns(index))

    def flush(self, carry):
        """The segment boundary's telemetry flush (one device_get), as the
        program's scan driver does -> (carry, per-round records)."""
        rows, ring = obs_ring.flush(carry[-1])
        return carry[:-1] + (ring,), rows

    def program_outputs(self, carry, losses, rows) -> dict:
        """What the first segment produced, in the reference's terms."""
        return {
            'loss': [float(x) for x in np.asarray(losses)],
            'q': [np.asarray(r.q) for r in rows],
            'p': [np.asarray(r.p) for r in rows],
            'sign_ok': [np.asarray(r.sign_ok) for r in rows],
            'mod_ok': [np.asarray(r.mod_ok) for r in rows],
            'agreement': [float(r.agreement) if r.agreement is not None
                          else float('nan') for r in rows],
            'alloc_iters': [int(r.alloc_iters) for r in rows],
            'objective': [float(r.alloc_objective) for r in rows],
            'params': np.asarray(ref.flatten(carry[0])),
            'gbar': np.asarray(carry[1]),
        }

    def release(self):
        """Drop the program's state before the reference runs."""
        self.sim = self.compiled = None

    # -- the reference ------------------------------------------------------
    def reference(self, run: dict) -> dict:
        """The float32 reference over the segment's rounds, transmitting
        with the (q, p) that ``run`` transmitted with."""
        return self._reference('float32', list(zip(run['q'], run['p'])))

    def control(self) -> dict:
        """The control: the reference in bfloat16 put in the program's
        place, transmitting with its own float64 allocation."""
        return self._reference('bfloat16', None)

    def _reference(self, dtype, forced) -> dict:
        xs, ys, params = self.inputs()
        op = dict(self.config['fl'])
        op['allocation_max_iters'] = op['allocation_max_iters'] or 6
        op['allocation_tol'] = op['allocation_tol'] or 1e-5
        return ref.run(self.seed, params, xs, ys, op, self.wire,
                       self.seg_len, dtype, self.order, forced)

    def readings(self, run: dict, refr: dict) -> dict:
        """Every number the comparison can hold to a limit."""
        params0 = np.asarray(ref.flatten(self.weights()))
        sizes = [int(np.prod(ref.SHAPES[k])) for k in ref.LEAVES]
        return checks.fl_readings(run, refr, params0,
                                  dict(zip(ref.LEAVES, sizes)))

    # -- what the per-layer metrics need --------------------------------------
    def hlo_text(self) -> str:
        return self.compiled.as_text()


def _rolled(solve):
    """The eq. (28) solve with its answer altered where it is produced:
    every client gets the next client's (q, p)."""
    def rolled(*args, **kwargs):
        s = solve(*args, **kwargs)
        return s._replace(q=jnp.roll(s.q, 1, axis=-1),
                          p=jnp.roll(s.p, 1, axis=-1))
    return rolled

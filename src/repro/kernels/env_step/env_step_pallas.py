"""Fused env-step + auto-reset Pallas kernels.

One kernel per physics env (pendulum / cartpole / cheetah): the whole
per-step pipeline — physics update, reward, termination test, and the
auto-reset select against precomputed reset candidates — runs over
``(B,)`` tiles with every state leaf resident in VMEM, replacing the
~15 separate elementwise XLA ops the batched reference lowers to with
one launch per step. The batch lives on the *lane* axis (blocks are
``(leaf_rank, b_block)`` with state scalars as ``(1, b_block)`` rows),
so B=1k–100k instances stream through in ``b_block``-wide tiles.

Each kernel body evaluates *exactly* the reference expressions
(``ref.<env>_step_batch_ref``) in the same order; parity tests assert
EXACT equality on int/bool leaves, the auto-reset select, and the full
pendulum/cheetah trees, and a measured few-ulp bound on cartpole's f32
arithmetic — XLA CPU FMA-contracts per fusion context, so two
differently-shaped compilations of the *same* ops (the ``(B,)`` ref vs
the tiled interpreted kernel) are not bitwise-stable against each
other; strict-rounding recomputation sides with the kernel where they
disagree. Reset candidates are inputs
(reset
sampling needs ``jax.random``; ``envs.base.auto_reset_batch`` draws them
outside) and ``done`` is returned as an int32 0/1 mask (the dispatcher
restores bool) — booleans stay internal to the kernel.

The kernels compile through Mosaic on TPU (``kernels/select.py``);
interpret mode is the CPU correctness harness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.env_step import ref as R


def _pad_lanes(x: jnp.ndarray, bp: int) -> jnp.ndarray:
    """(k, B) -> (k, bp) zero-padded on the lane (batch) axis."""
    return jnp.pad(x, ((0, 0), (0, bp - x.shape[1])))


def _rows(bp, *xs):
    """Each (B,) array -> one (1, bp) lane row."""
    return [_pad_lanes(x[None, :], bp) for x in xs]


# ================================================================ pendulum
def _pendulum_kernel(th_ref, td_ref, t_ref, a_ref,
                     rth_ref, rtd_ref, rt_ref, robs_ref,
                     oth_ref, otd_ref, ot_ref, oobs_ref, orew_ref,
                     odone_ref, *, max_episode_steps, reward_scale,
                     max_torque):
    th, thdot, t = th_ref[...], td_ref[...], t_ref[...]
    u = jnp.clip(a_ref[...], -max_torque, max_torque)
    cost = R._angle_norm(th) ** 2 + 0.1 * thdot ** 2 + 0.001 * u ** 2
    thdot = thdot + (3 * R.PENDULUM_G / (2 * R.PENDULUM_L) * jnp.sin(th)
                     + 3.0 / (R.PENDULUM_M * R.PENDULUM_L ** 2) * u) \
        * R.PENDULUM_DT
    thdot = jnp.clip(thdot, -R.PENDULUM_MAX_SPEED, R.PENDULUM_MAX_SPEED)
    th = th + thdot * R.PENDULUM_DT
    t = t + 1
    done = t >= max_episode_steps
    reward = -cost
    if reward_scale != 1.0:
        reward = reward * reward_scale
    obs = jnp.concatenate([jnp.cos(th), jnp.sin(th),
                           thdot / R.PENDULUM_MAX_SPEED], axis=0)
    oth_ref[...] = jnp.where(done, rth_ref[...], th)
    otd_ref[...] = jnp.where(done, rtd_ref[...], thdot)
    ot_ref[...] = jnp.where(done, rt_ref[...], t)
    oobs_ref[...] = jnp.where(done, robs_ref[...], obs)
    orew_ref[...] = reward
    odone_ref[...] = done.astype(jnp.int32)


def pendulum_step_pallas(state, actions, reset_state, reset_obs, *,
                         max_episode_steps, reward_scale, max_torque,
                         b_block: int = 512, interpret: bool = True):
    th, thdot, t = state
    rth, rtd, rt = reset_state
    B = th.shape[0]
    b_block = min(b_block, B)
    nb = pl.cdiv(B, b_block)
    bp = nb * b_block

    ins = _rows(bp, th, thdot, t, actions[:, 0], rth, rtd, rt)
    ins.append(_pad_lanes(reset_obs.T, bp))                    # (3, bp)

    row = pl.BlockSpec((1, b_block), lambda bi: (0, bi))
    obs_spec = pl.BlockSpec((3, b_block), lambda bi: (0, bi))
    kernel = functools.partial(_pendulum_kernel,
                               max_episode_steps=max_episode_steps,
                               reward_scale=reward_scale,
                               max_torque=max_torque)
    f32 = jax.ShapeDtypeStruct((1, bp), jnp.float32)
    i32 = jax.ShapeDtypeStruct((1, bp), jnp.int32)
    oth, otd, ot, oobs, orew, odone = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[row] * 7 + [obs_spec],
        out_specs=[row, row, row, obs_spec, row, row],
        out_shape=[f32, f32, i32,
                   jax.ShapeDtypeStruct((3, bp), jnp.float32), f32, i32],
        interpret=interpret,
    )(*ins)
    return ((oth[0, :B], otd[0, :B], ot[0, :B]), oobs[:, :B].T,
            orew[0, :B], odone[0, :B].astype(bool))


# ================================================================ cartpole
def _cartpole_kernel(x_ref, xd_ref, th_ref, td_ref, t_ref, a_ref,
                     rx_ref, rxd_ref, rth_ref, rtd_ref, rt_ref, robs_ref,
                     ox_ref, oxd_ref, oth_ref, otd_ref, ot_ref, oobs_ref,
                     orew_ref, odone_ref, *, max_episode_steps,
                     reward_scale, force_max):
    x, xdot, th, thdot, t = (x_ref[...], xd_ref[...], th_ref[...],
                             td_ref[...], t_ref[...])
    a0 = a_ref[...]
    force = jnp.clip(a0, -1.0, 1.0) * force_max
    total_m = R.CARTPOLE_M_CART + R.CARTPOLE_M_POLE
    pm_l = R.CARTPOLE_M_POLE * R.CARTPOLE_L_POLE
    costh, sinth = jnp.cos(th), jnp.sin(th)
    temp = (force + pm_l * thdot ** 2 * sinth) / total_m
    th_acc = ((R.CARTPOLE_GRAVITY * sinth - costh * temp)
              / (R.CARTPOLE_L_POLE
                 * (4.0 / 3.0 - R.CARTPOLE_M_POLE * costh ** 2 / total_m)))
    x_acc = temp - pm_l * th_acc * costh / total_m
    x = x + R.CARTPOLE_DT * xdot
    xdot = xdot + R.CARTPOLE_DT * x_acc
    th = th + R.CARTPOLE_DT * thdot
    thdot = thdot + R.CARTPOLE_DT * th_acc
    t = t + 1
    fell = ((jnp.abs(x) > R.CARTPOLE_X_LIMIT)
            | (jnp.abs(th) > R.CARTPOLE_TH_LIMIT))
    done = fell | (t >= max_episode_steps)
    reward = 1.0 - 0.01 * a0 ** 2 - 1.0 * fell
    if reward_scale != 1.0:
        reward = reward * reward_scale
    obs = jnp.concatenate([x, xdot, th, thdot], axis=0)
    ox_ref[...] = jnp.where(done, rx_ref[...], x)
    oxd_ref[...] = jnp.where(done, rxd_ref[...], xdot)
    oth_ref[...] = jnp.where(done, rth_ref[...], th)
    otd_ref[...] = jnp.where(done, rtd_ref[...], thdot)
    ot_ref[...] = jnp.where(done, rt_ref[...], t)
    oobs_ref[...] = jnp.where(done, robs_ref[...], obs)
    orew_ref[...] = reward
    odone_ref[...] = done.astype(jnp.int32)


def cartpole_step_pallas(state, actions, reset_state, reset_obs, *,
                         max_episode_steps, reward_scale, force_max,
                         b_block: int = 512, interpret: bool = True):
    x, xdot, th, thdot, t = state
    rx, rxd, rth, rtd, rt = reset_state
    B = x.shape[0]
    b_block = min(b_block, B)
    nb = pl.cdiv(B, b_block)
    bp = nb * b_block

    ins = _rows(bp, x, xdot, th, thdot, t, actions[:, 0],
                rx, rxd, rth, rtd, rt)
    ins.append(_pad_lanes(reset_obs.T, bp))                    # (4, bp)

    row = pl.BlockSpec((1, b_block), lambda bi: (0, bi))
    obs_spec = pl.BlockSpec((4, b_block), lambda bi: (0, bi))
    kernel = functools.partial(_cartpole_kernel,
                               max_episode_steps=max_episode_steps,
                               reward_scale=reward_scale,
                               force_max=force_max)
    f32 = jax.ShapeDtypeStruct((1, bp), jnp.float32)
    i32 = jax.ShapeDtypeStruct((1, bp), jnp.int32)
    ox, oxd, oth, otd, ot, oobs, orew, odone = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[row] * 11 + [obs_spec],
        out_specs=[row, row, row, row, row, obs_spec, row, row],
        out_shape=[f32, f32, f32, f32, i32,
                   jax.ShapeDtypeStruct((4, bp), jnp.float32), f32, i32],
        interpret=interpret,
    )(*ins)
    return ((ox[0, :B], oxd[0, :B], oth[0, :B], otd[0, :B], ot[0, :B]),
            oobs[:, :B].T, orew[0, :B], odone[0, :B].astype(bool))


# ================================================================= cheetah
def _cheetah_kernel(th_ref, om_ref, vx_ref, pi_ref, t_ref, a_ref,
                    rth_ref, rom_ref, rvx_ref, rpi_ref, rt_ref, robs_ref,
                    oth_ref, oom_ref, ovx_ref, opi_ref, ot_ref, oobs_ref,
                    orew_ref, odone_ref, *, max_episode_steps,
                    reward_scale, ctrl_cost):
    th, om = th_ref[...], om_ref[...]                       # (6, b)
    vx, pitch, t = vx_ref[...], pi_ref[...], t_ref[...]     # (1, b)
    a = jnp.clip(a_ref[...], -1.0, 1.0)
    # jnp.roll(th, 1, axis=0) written as a concatenate so the body stays
    # lowerable on every Pallas backend; identical values
    rolled = jnp.concatenate([th[-1:], th[:-1]], axis=0)
    neighbour = R.CHEETAH_COUPLING * (rolled - th)
    om = om + R.CHEETAH_DT * (R.CHEETAH_GEAR * a - R.CHEETAH_DAMPING * om
                              - R.CHEETAH_STIFFNESS * th + neighbour)
    th = th + R.CHEETAH_DT * om
    thrust = jnp.mean(jnp.sin(th[:-1] - th[1:]) * (om[:-1] - om[1:]),
                      axis=0, keepdims=True)
    vx = 0.9 * vx + R.CHEETAH_DT * (8.0 * thrust)
    pitch = 0.95 * pitch + 0.05 * jnp.mean(th, axis=0, keepdims=True)
    t = t + 1
    reward = vx - ctrl_cost * jnp.sum(a ** 2, axis=0, keepdims=True)
    if reward_scale != 1.0:
        reward = reward * reward_scale
    done = t >= max_episode_steps
    obs = jnp.concatenate([th, om, vx, pitch], axis=0)      # (14, b)
    oth_ref[...] = jnp.where(done, rth_ref[...], th)
    oom_ref[...] = jnp.where(done, rom_ref[...], om)
    ovx_ref[...] = jnp.where(done, rvx_ref[...], vx)
    opi_ref[...] = jnp.where(done, rpi_ref[...], pitch)
    ot_ref[...] = jnp.where(done, rt_ref[...], t)
    oobs_ref[...] = jnp.where(done, robs_ref[...], obs)
    orew_ref[...] = reward
    odone_ref[...] = done.astype(jnp.int32)


def cheetah_step_pallas(state, actions, reset_state, reset_obs, *,
                        max_episode_steps, reward_scale, ctrl_cost,
                        b_block: int = 512, interpret: bool = True):
    th, om, vx, pitch, t = state
    rth, rom, rvx, rpi, rt = reset_state
    B = vx.shape[0]
    NJ = th.shape[1]
    b_block = min(b_block, B)
    nb = pl.cdiv(B, b_block)
    bp = nb * b_block

    ins = [_pad_lanes(th.T, bp), _pad_lanes(om.T, bp)]
    ins += _rows(bp, vx, pitch, t)
    ins += [_pad_lanes(actions.T, bp),
            _pad_lanes(rth.T, bp), _pad_lanes(rom.T, bp)]
    ins += _rows(bp, rvx, rpi, rt)
    ins.append(_pad_lanes(reset_obs.T, bp))                 # (14, bp)

    row = pl.BlockSpec((1, b_block), lambda bi: (0, bi))
    jnt = pl.BlockSpec((NJ, b_block), lambda bi: (0, bi))
    obs_spec = pl.BlockSpec((2 * NJ + 2, b_block), lambda bi: (0, bi))
    kernel = functools.partial(_cheetah_kernel,
                               max_episode_steps=max_episode_steps,
                               reward_scale=reward_scale,
                               ctrl_cost=ctrl_cost)
    f32 = jax.ShapeDtypeStruct((1, bp), jnp.float32)
    i32 = jax.ShapeDtypeStruct((1, bp), jnp.int32)
    jf32 = jax.ShapeDtypeStruct((NJ, bp), jnp.float32)
    oth, oom, ovx, opi, ot, oobs, orew, odone = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[jnt, jnt, row, row, row, jnt, jnt, jnt, row, row, row,
                  obs_spec],
        out_specs=[jnt, jnt, row, row, row, obs_spec, row, row],
        out_shape=[jf32, jf32, f32, f32, i32,
                   jax.ShapeDtypeStruct((2 * NJ + 2, bp), jnp.float32),
                   f32, i32],
        interpret=interpret,
    )(*ins)
    return ((oth[:, :B].T, oom[:, :B].T, ovx[0, :B], opi[0, :B],
             ot[0, :B]), oobs[:, :B].T, orew[0, :B],
            odone[0, :B].astype(bool))


STEP_BATCH_PALLAS = {
    "pendulum": pendulum_step_pallas,
    "cartpole": cartpole_step_pallas,
    "cheetah": cheetah_step_pallas,
}

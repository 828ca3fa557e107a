"""LteTtiController — the batched per-TTI engine for every cell at once.

Reference parity (SURVEY.md §3.4 call stack): upstream clocks each eNB
with per-subframe events (LteEnbPhy::StartSubFrame), each of which runs
the FF-MAC scheduler, transmits over MultiModelSpectrumChannel (an
O(eNB×UE) loop), collects interference chunks, and decodes TBs per UE
(LteSpectrumPhy::StartRxData → LteInterference → LteMiErrorModel).

TPU-first redesign: LTE subframes are *synchronous network-wide*, so
the whole per-TTI PHY — every cell's PSD, every UE's per-RB SINR, MI,
BLER and decode draw, both directions — is ONE jitted kernel call
(ops/lte.py::tti_phy_step) driven by ONE simulator event per TTI.  The
host side keeps what is genuinely sequential/stateful: FF-MAC
scheduling decisions, RLC segmentation, HARQ bookkeeping, RRC state.
This is the 1 ms natural conservative window SURVEY.md §7 hard-part 1
identifies ("LTE is easier: 1 ms TTI is a natural window").

Timing-model notes (deviations, all fixed offsets):
- TB decode outcome is computed in the transmitting TTI's event; HARQ
  retransmissions run at +8 TTIs (the upstream HARQ RTT), CQI feedback
  applies after ``CQI_DELAY_TTIS``.
- Uplink uses the same type-0 RBG allocation as downlink (upstream UL
  is contiguous SC-FDMA allocation).
- UE→eNB and eNB→UE path gains are reciprocal (same loss model, no
  per-direction fading this round).
"""

from __future__ import annotations

import numpy as np

from tpudes.core.nstime import MilliSeconds
from tpudes.core.rng import RngSeedManager
from tpudes.core.simulator import Simulator
from tpudes.models.lte.scheduler import (
    HARQ_MAX_TX,
    HARQ_RTT_TTIS,
    Allocation,
    HarqTb,
    SchedCandidate,
    rbg_size_for,
)
from tpudes.ops.lte import RB_BANDWIDTH_HZ

CQI_DELAY_TTIS = 3


class LteTtiController:
    """One instance per LteHelper: owns the synchronized TTI clock and
    the batched PHY state for all installed cells and UEs."""

    def __init__(self, pathloss_model, n_rb: int = 25):
        self.pathloss = pathloss_model
        self.n_rb = n_rb
        self.rbg_size = rbg_size_for(n_rb)
        self.n_rbg = (n_rb + self.rbg_size - 1) // self.rbg_size
        self.enbs: list = []
        self.ues: list = []
        self.tti = 0
        self._started = False
        self.lifted = False   # set by parallel.lift: device engine owns the run
        self._dirty = True
        self._static_geometry = True
        #: True once a windowed engine has driven refresh_window_cache:
        #: the per-TTI event then trusts the window snapshot instead of
        #: re-evaluating mobile geometry at every event
        self._windowed = False
        # second BatchableRegistry consumer beside YansWifiChannel: the
        # windowed engine refreshes the per-TTI SINR evaluation tables
        # once per window instead of once per TTI event
        from tpudes.parallel.engine import BatchableRegistry

        BatchableRegistry.register(self)
        # device-side constants (built lazily)
        self._gain_dl = None          # (E, U)
        self._gain_ul_eff = None      # (U, U): v's gain at u's serving eNB
        self._serving = None          # (U,)
        self._harq_dl: dict[int, list[HarqTb]] = {}
        self._harq_ul: dict[int, list[HarqTb]] = {}
        self._cqi_dl = None           # (U,) applied CQI at the eNB
        self._cqi_ul = None
        self._cqi_queue: list = []    # (apply_tti, cqi_dl, cqi_ul)
        self._key = None
        self._jit_step = None
        self.handover_algorithm = None   # set via LteHelper
        self.ffr_algorithm = None        # set via LteHelper (RBG masks)
        self.last_alloc: dict = {}       # per-direction (U, n_rb) masks
        self.x2_enabled = False          # AddX2Interface arms execution
        self.handover_log: list = []     # (tti, imsi, from_cell, to_cell)
        self.stats = {
            "dl_tbs": 0, "dl_ok": 0, "dl_harq_retx": 0, "dl_drops": 0,
            "ul_tbs": 0, "ul_ok": 0, "ul_harq_retx": 0, "ul_drops": 0,
            "ttis": 0, "handovers": 0,
        }

    # --- wiring -----------------------------------------------------------
    def add_enb(self, dev) -> None:
        self.enbs.append(dev)
        self._harq_dl[len(self.enbs) - 1] = []
        self._harq_ul[len(self.enbs) - 1] = []
        self._dirty = True

    def add_ue(self, dev) -> None:
        self.ues.append(dev)
        self._dirty = True

    def attach(self, ue_dev, enb_dev) -> None:
        ctx = enb_dev.rrc.add_ue(ue_dev)
        ue_dev.rrc.connect(enb_dev, ctx.rnti)
        self._dirty = True
        self.start()

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        import jax

        self._key = jax.random.PRNGKey(
            (RngSeedManager.GetSeed() * 2654435761 + RngSeedManager.GetRun())
            & 0x7FFFFFFF
        )
        Simulator.Schedule(MilliSeconds(0), self._tti_event)

    # --- geometry / arrays ------------------------------------------------
    def _positions(self, devs) -> np.ndarray:
        from tpudes.models.mobility import MobilityModel

        pos = np.zeros((len(devs), 3), dtype=np.float64)
        for i, d in enumerate(devs):
            mob = d.GetNode().GetObject(MobilityModel)
            if mob is None:
                raise RuntimeError("LTE devices need a mobility model")
            p = mob.GetPosition()
            pos[i] = (p.x, p.y, p.z)
            if "ConstantPosition" not in type(mob).__name__:
                self._static_geometry = False
        return pos

    def _rebuild(self) -> None:
        self._dirty = False
        e, u = len(self.enbs), len(self.ues)
        if e == 0 or u == 0:
            return
        self._static_geometry = True
        self._compute_gain_dl()
        serving = np.full((u,), -1, dtype=np.int64)
        enb_index = {id(dev): i for i, dev in enumerate(self.enbs)}
        for i, ue in enumerate(self.ues):
            s = ue.rrc.serving_enb
            if s is not None:
                serving[i] = enb_index[id(s)]
        self._serving = serving
        self._ue_index = {id(dev): i for i, dev in enumerate(self.ues)}
        # UL CQI is measured SRS-style: intra-cell sounding is orthogonal,
        # so co-served transmitters must NOT appear as interferers in the
        # reference scenario (only inter-cell UEs + noise do).  Without
        # this mask every same-cell UE looks like a full-band interferer
        # and all but one UE per cell report CQI 0 permanently.
        # attachment-aware: an unattached UE (serving -1) is nobody's
        # cell-mate — it stays a real interferer everywhere
        same_cell = (serving[:, None] == serving[None, :]) & (
            serving[:, None] >= 0
        )                                                   # (v, u)
        # kept for the geometry-only refresh (attachment topology: only
        # a handover/attach — which sets _dirty — can change it)
        self._srs_mask = np.where(
            same_cell & ~np.eye(u, dtype=bool), 0.0, 1.0
        )
        self._publish_gain_residents()
        if self._cqi_dl is None or len(self._cqi_dl) != u:
            self._cqi_dl = np.zeros((u,), dtype=np.int64)
            self._cqi_ul = np.zeros((u,), dtype=np.int64)
        # full-power reference PSDs (RS-like) for CQI measurement; under
        # FFR each cell's reference occupies only its allowed subband,
        # so CQI (and hence MCS) sees the reuse pattern's interference
        def _cell_rbs(e_idx: int) -> list[int]:
            if self.ffr_algorithm is None:
                return list(range(self.n_rb))
            return self._rbgs_to_rbs(
                self.ffr_algorithm.allowed_rbgs(e_idx, self.n_rbg)
            )

        self._ref_psd_dl = np.zeros((e, self.n_rb))
        for i, enb in enumerate(self.enbs):
            p_w = 10.0 ** ((enb.phy.tx_power_dbm - 30.0) / 10.0)
            self._ref_psd_dl[i, _cell_rbs(i)] = p_w / (
                self.n_rb * RB_BANDWIDTH_HZ
            )
        self._ref_psd_ul = np.zeros((u, self.n_rb))
        for i, ue in enumerate(self.ues):
            p_w = 10.0 ** ((ue.phy.tx_power_dbm - 30.0) / 10.0)
            rbs = _cell_rbs(int(serving[i])) if serving[i] >= 0 else list(
                range(self.n_rb)
            )
            self._ref_psd_ul[i, rbs] = p_w / (self.n_rb * RB_BANDWIDTH_HZ)
        nf_ue = {float(ue.phy.noise_figure_db) for ue in self.ues}
        nf_enb = {float(enb.phy.noise_figure_db) for enb in self.enbs}
        if len(nf_ue) > 1 or len(nf_enb) > 1:
            raise RuntimeError(
                "batched TTI path assumes uniform noise figures per side"
            )
        self._noise_dl = self.ues[0].phy.noise_psd
        self._noise_ul = self.enbs[0].phy.noise_psd
        if self._jit_step is None:
            import jax

            from tpudes.ops.lte import tti_phy_step

            # both directions fused into ONE device call per TTI: the
            # TTI event makes exactly one dispatch and one device_get
            # (SURVEY.md §7 hard part 3)
            def both(dl_args, ul_args, ul_ref_gain, noise_dl, noise_ul, k):
                import jax as _jax

                k_dl, k_ul = _jax.random.split(k)
                return (
                    tti_phy_step(*dl_args, k_dl, noise_dl),
                    tti_phy_step(*ul_args, k_ul, noise_ul, ul_ref_gain),
                )

            self._jit_step = jax.jit(both)

    # --- per-window batched refresh (JaxSimulatorImpl contract) -----------
    def refresh_window_cache(self) -> None:
        """Rebuild geometry + the batched per-TTI SINR reference tables
        (gain matrices, reference PSDs) ONCE per conservative window.
        Mobile graphs otherwise pay one full rebuild per TTI *event*;
        under the windowed engine every TTI inside the window reads the
        window-start snapshot — the same granted-time-window geometry
        contract YansWifiChannel's pair-table cache follows.

        This whole path is the FALLBACK behind device-resident mobility
        (``tpudes.parallel.lte_sm`` lifts moving UEs into the scan):
        when it does run, ``TPUDES_DEVICE_GEOM`` selects between the
        geometry-only refresh (recompute exactly the position-dependent
        arrays; the attachment-topology tables were built once) and the
        legacy full rebuild — bit-equal by construction, since the
        geometry-only path runs the same math on the same inputs."""
        from tpudes.obs.geometry import GeomTelemetry
        from tpudes.ops.mobility import device_geom_enabled

        if self._dirty:
            if self.enbs and self.ues:
                self._rebuild()
        elif not self._static_geometry and self.enbs and self.ues:
            if device_geom_enabled():
                self._refresh_geometry()
            else:
                self._rebuild()
            GeomTelemetry.record_host("lte_ctrl")
        self._windowed = True

    def _refresh_geometry(self) -> None:
        """The position-dependent slice of :meth:`_rebuild` — gain
        matrices (+ scene loss) and their device residents, nothing
        else.  Bit-equal to a full rebuild BY CONSTRUCTION: both paths
        call the same two helpers below; this one just skips
        re-deriving the attachment topology (serving maps, SRS mask,
        reference PSDs, noise figures, the jitted step) that only a
        ``_dirty``-setting event can change."""
        self._compute_gain_dl()
        self._publish_gain_residents()

    def _compute_gain_dl(self) -> None:
        """positions → distance → loss chain (+ scene effects) →
        ``_gain_dl`` — the geometry half shared by :meth:`_rebuild`
        and :meth:`_refresh_geometry`."""
        import jax.numpy as jnp

        from tpudes.models.lte.scene import scene_loss_db

        pos_e = self._positions(self.enbs)
        pos_u = self._positions(self.ues)
        d = np.sqrt(
            ((pos_e[:, None, :] - pos_u[None, :, :]) ** 2).sum(-1)
        )  # (E, U)
        # loss chain evaluated as one batched kernel call: gain below
        # unity, reciprocal between directions; buildings (wall
        # penetration) + antennas (directional gain) ride the shared
        # scene implementation (one copy with the REM helper)
        loss_db = -np.asarray(
            self.pathloss.batch_rx_power(jnp.zeros(()), jnp.asarray(d))
        )
        loss_db = loss_db + scene_loss_db(self.enbs, pos_e, pos_u)
        self._gain_dl = 10.0 ** (-loss_db / 10.0)               # (E, U)

    def _publish_gain_residents(self) -> None:
        """``_gain_dl`` + the (attachment-topology) serving map / SRS
        mask → the UL effective gains and the device-resident arrays
        the TTI step consumes — static across TTIs, so device-resident
        once instead of re-shipped per dispatch."""
        import jax.numpy as jnp

        # v transmitting → power at u's serving eNB: (U, U)
        safe = np.maximum(self._serving, 0)
        self._gain_ul_eff = self._gain_dl.T[:, safe].astype(np.float64)
        self._gain_ul_ref = jnp.asarray(self._gain_ul_eff * self._srs_mask)
        self._gain_dl_dev = jnp.asarray(self._gain_dl)
        self._gain_ul_dev = jnp.asarray(self._gain_ul_eff)

    def _rbgs_to_rbs(self, rbgs) -> list[int]:
        """TS 36.213 type-0: expand RBG indices to RB indices (one
        implementation for allocation AND the CQI reference grid)."""
        return [
            r
            for g in rbgs
            for r in range(
                g * self.rbg_size, min((g + 1) * self.rbg_size, self.n_rb)
            )
        ]

    # --- per-TTI scheduling (host side) -----------------------------------
    def _cell_ue_indices(self, e_idx: int) -> list[int]:
        return [i for i in range(len(self.ues)) if self._serving[i] == e_idx]

    def _schedule_direction(self, direction: str):
        """Run HARQ-first + FF-MAC allocation for every cell; returns the
        packed (alloc, mcs, tb_bits, mi_acc, tx_psd, served) arrays."""
        u = len(self.ues)
        e = len(self.enbs)
        alloc = np.zeros((u, self.n_rb), dtype=bool)
        mcs = np.zeros((u,), dtype=np.int64)
        tb_bits = np.zeros((u,), dtype=np.float64)
        mi_acc = np.zeros((u,), dtype=np.float64)
        tx_psd = np.zeros((e, self.n_rb)) if direction == "dl" else np.zeros(
            (u, self.n_rb)
        )
        tb_by_ue: dict[int, HarqTb] = {}
        harq_map = self._harq_dl if direction == "dl" else self._harq_ul
        cqi = self._cqi_dl if direction == "dl" else self._cqi_ul

        for e_idx, enb in enumerate(self.enbs):
            members = self._cell_ue_indices(e_idx)
            if not members:
                continue
            if self.ffr_algorithm is not None:
                free = list(
                    self.ffr_algorithm.allowed_rbgs(e_idx, self.n_rbg)
                )
            else:
                free = list(range(self.n_rbg))
            allocs: list[Allocation] = []
            # 1. HARQ retransmissions due this TTI
            pending = harq_map[e_idx]
            still: list[HarqTb] = []
            for tb in pending:
                ue_i = tb.rnti_ue_index
                if tb.due_tti > self.tti or ue_i in tb_by_ue:
                    still.append(tb)
                    continue
                if len(free) < tb.n_rbg:
                    tb.due_tti = self.tti + 1
                    still.append(tb)
                    continue
                take, free = free[: tb.n_rbg], free[tb.n_rbg:]
                allocs.append(
                    Allocation(tb.rnti, take, tb.mcs, tb.tb_bytes, harq=tb)
                )
                self.stats[f"{direction}_harq_retx"] += 1
            harq_map[e_idx] = still
            # 2. new transmissions
            scheduler = (
                enb.scheduler if direction == "dl" else enb.ul_scheduler
            )
            rnti_to_ue = {
                ctx.rnti: self._ue_index[id(ctx.ue_device)]
                for ctx in enb.rrc.ues.values()
            }
            candidates = []
            for rnti, ctx in enb.rrc.ues.items():
                ue_i = rnti_to_ue[rnti]
                if ue_i in tb_by_ue or any(
                    tb.rnti == rnti for tb in allocs
                ):
                    continue  # one TB per UE per TTI
                queue = sum(
                    (b.dl_tx if direction == "dl" else b.ul_tx).BufferBytes()
                    for b in ctx.bearers.values()
                )
                if queue <= 0 or cqi[ue_i] < 1:
                    continue
                candidates.append(
                    SchedCandidate(rnti, int(cqi[ue_i]), queue)
                )
            allocs.extend(
                scheduler.schedule(self.tti, candidates, free, self.rbg_size)
            )
            # 3. pack allocations into arrays + pull RLC PDUs
            for a in allocs:
                ue_i = rnti_to_ue.get(a.rnti)
                if ue_i is None or ue_i in tb_by_ue:
                    continue
                ctx = enb.rrc.ues[a.rnti]
                if a.harq is None:
                    pdu = None
                    for b in sorted(ctx.bearers):
                        rlc = (
                            ctx.bearers[b].dl_tx
                            if direction == "dl"
                            else ctx.bearers[b].ul_tx
                        )
                        pdu = rlc.NotifyTxOpportunity(a.tb_bytes)
                        if pdu is not None:
                            tb = HarqTb(
                                a.rnti, pdu, a.mcs, len(a.rbgs), a.tb_bytes
                            )
                            tb.bearer = ctx.bearers[b]
                            tb.tx_count = 1
                            break
                    if pdu is None:
                        continue
                    self.stats[f"{direction}_tbs"] += 1
                else:
                    tb = a.harq
                    tb.tx_count += 1
                tb.rnti_ue_index = ue_i
                tb_by_ue[ue_i] = tb
                rbs = self._rbgs_to_rbs(a.rbgs)
                alloc[ue_i, rbs] = True
                mcs[ue_i] = a.mcs
                tb_bits[ue_i] = a.tb_bytes * 8.0
                mi_acc[ue_i] = tb.mi_acc
                if direction == "dl":
                    p_w = 10.0 ** ((enb.phy.tx_power_dbm - 30.0) / 10.0)
                    tx_psd[e_idx, rbs] += p_w / (self.n_rb * RB_BANDWIDTH_HZ)
                else:
                    ue = self.ues[ue_i]
                    p_w = 10.0 ** ((ue.phy.tx_power_dbm - 30.0) / 10.0)
                    # UL concentrates the UE's power in its allocated RBs
                    tx_psd[ue_i, rbs] = p_w / (len(rbs) * RB_BANDWIDTH_HZ)
        return alloc, mcs, tb_bits, mi_acc, tx_psd, tb_by_ue

    # --- handover (A3 measurement + X2-lite execution) --------------------
    def _evaluate_handover(self) -> None:
        from tpudes.models.lte.handover import MEASUREMENT_PERIOD_TTIS

        if (
            self.handover_algorithm is None
            or not self.x2_enabled
            or self.tti % MEASUREMENT_PERIOD_TTIS != 0
            or self._gain_dl is None
            or len(self.enbs) < 2
        ):
            return
        # RSRP per (E, U) from the already-batched gain matrix
        tx_dbm = np.array([e.phy.tx_power_dbm for e in self.enbs])
        rsrp_dbm = tx_dbm[:, None] + 10.0 * np.log10(
            np.maximum(self._gain_dl, 1e-30)
        )
        moves = []
        for u_i, ue in enumerate(self.ues):
            s = int(self._serving[u_i])
            if s < 0:
                continue
            target = self.handover_algorithm.evaluate(
                self.tti, u_i, s, rsrp_dbm[:, u_i]
            )
            if target is not None and target != s:
                moves.append((u_i, s, target))
        for u_i, s, target in moves:
            self._execute_handover(u_i, s, target)

    def _execute_handover(self, ue_index: int, src_idx: int, dst_idx: int):
        """X2-lite: move the UeContext (bearers intact — the lossless
        forwarding analog), flush in-flight HARQ at the source (the MAC
        reset), reconnect the UE, mark geometry dirty."""
        ue = self.ues[ue_index]
        source, target = self.enbs[src_idx], self.enbs[dst_idx]
        ctx = source.rrc.remove_ue(ue.rrc.rnti)
        if ctx is None:
            return
        for harq_map in (self._harq_dl, self._harq_ul):
            harq_map[src_idx] = [
                tb for tb in harq_map[src_idx]
                if tb.rnti_ue_index != ue_index
            ]
        new_ctx = target.rrc.add_ue(ue)
        new_ctx.bearers = ctx.bearers
        for b in new_ctx.bearers.values():
            b.ul_rx.rx_sdu_callback = target.receive_ul_sdu
        ue.rrc.connect(target, new_ctx.rnti)
        self.stats["handovers"] += 1
        self.handover_log.append(
            (self.tti, ue.GetImsi(), source.GetCellId(), target.GetCellId())
        )
        self._dirty = True

    # --- the TTI event ----------------------------------------------------
    def _tti_event(self) -> None:
        import jax
        import jax.numpy as jnp

        if self.lifted:
            return  # the lifted device program runs the scenario instead
        if self._dirty:
            self._rebuild()
        elif not self._static_geometry and not self._windowed:
            # per-event fallback: no windowed engine drives the registry,
            # so mobile geometry must be re-evaluated at every TTI —
            # geometry-only unless the kill switch wants the legacy
            # full rebuild (bit-equal either way; see _refresh_geometry)
            from tpudes.obs.geometry import GeomTelemetry
            from tpudes.ops.mobility import device_geom_enabled

            if device_geom_enabled():
                self._refresh_geometry()
            else:
                self._rebuild()
            GeomTelemetry.record_host("lte_ctrl")
        self._evaluate_handover()
        if self._dirty:
            self._rebuild()  # a handover just moved serving cells
        u, e = len(self.ues), len(self.enbs)
        if u and e:
            self.stats["ttis"] += 1
            key = jax.random.fold_in(self._key, self.tti)
            served_bits_by_cell: dict[str, dict[int, dict[int, int]]] = {}

            # host side: both directions' scheduling first, then ONE
            # fused device call and ONE device_get
            sched = {d: self._schedule_direction(d) for d in ("dl", "ul")}
            #: (U, n_rb) bool allocation masks of the last TTI, per
            #: direction — stats/test visibility (RB-usage traces)
            self.last_alloc = {d: sched[d][0] for d in ("dl", "ul")}

            def pack(direction):
                alloc, mcs, tb_bits, mi_acc, tx_psd, _ = sched[direction]
                if direction == "dl":
                    gain, serving, ref = (
                        self._gain_dl_dev, self._serving, self._ref_psd_dl,
                    )
                else:
                    gain, serving, ref = (
                        self._gain_ul_dev, np.arange(u), self._ref_psd_ul,
                    )
                return (
                    jnp.asarray(tx_psd),
                    jnp.asarray(ref),
                    jnp.asarray(gain),
                    jnp.asarray(np.maximum(serving, 0), dtype=jnp.int32),
                    jnp.asarray(alloc),
                    jnp.asarray(mcs, dtype=jnp.int32),
                    jnp.asarray(tb_bits, dtype=jnp.float32),
                    jnp.asarray(mi_acc, dtype=jnp.float32),
                )

            out_dl, out_ul = jax.device_get(
                self._jit_step(
                    pack("dl"), pack("ul"), self._gain_ul_ref,
                    self._noise_dl, self._noise_ul, key
                )
            )
            for direction, (ok, _bler, cqi_meas, mi_new) in (
                ("dl", out_dl), ("ul", out_ul)
            ):
                tb_by_ue = sched[direction][5]
                served: dict[int, dict[int, int]] = {}
                for ue_i, tb in tb_by_ue.items():
                    e_idx = int(self._serving[ue_i])
                    if ok[ue_i]:
                        rx = (
                            tb.bearer.dl_rx
                            if direction == "dl"
                            else tb.bearer.ul_rx
                        )
                        rx.ReceivePdu(tb.pdu)
                        self.stats[f"{direction}_ok"] += 1
                        served.setdefault(e_idx, {})[tb.rnti] = int(
                            tb.tb_bytes * 8
                        )
                    elif tb.tx_count < HARQ_MAX_TX:
                        tb.mi_acc = float(mi_new[ue_i])
                        tb.due_tti = self.tti + HARQ_RTT_TTIS
                        harq_map = (
                            self._harq_dl if direction == "dl" else self._harq_ul
                        )
                        harq_map[e_idx].append(tb)
                    else:
                        self.stats[f"{direction}_drops"] += 1
                served_bits_by_cell[direction] = served
                if direction == "dl":
                    self._pending_cqi_dl = cqi_meas
                else:
                    self._pending_cqi_ul = cqi_meas

            # CQI feedback delay
            self._cqi_queue.append(
                (self.tti + CQI_DELAY_TTIS, self._pending_cqi_dl,
                 self._pending_cqi_ul)
            )
            while self._cqi_queue and self._cqi_queue[0][0] <= self.tti + 1:
                _, cqi_dl, cqi_ul = self._cqi_queue.pop(0)
                self._cqi_dl = cqi_dl
                self._cqi_ul = cqi_ul
            # PF averages (both directions)
            for e_idx, enb in enumerate(self.enbs):
                rntis = [c.rnti for c in enb.rrc.ues.values()]
                for sched, dirn in ((enb.scheduler, "dl"), (enb.ul_scheduler, "ul")):
                    if hasattr(sched, "end_tti"):
                        sched.end_tti(
                            served_bits_by_cell.get(dirn, {}).get(e_idx, {}),
                            rntis,
                        )
        self.tti += 1
        Simulator.Schedule(MilliSeconds(1), self._tti_event)

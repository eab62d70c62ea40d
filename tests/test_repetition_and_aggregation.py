import jax.numpy as jnp
import numpy as np
import pytest

from draco_tpu import aggregation
from draco_tpu.attacks import inject_plain
from draco_tpu.coding import repetition


class TestMajorityVote:
    def test_recovers_honest_under_minority_corruption(self, rng):
        n, r, d = 9, 3, 40
        code = repetition.build_repetition_code(n, r)
        honest = rng.randn(code.num_groups, d).astype(np.float32)
        grads = np.repeat(honest, r, axis=0)  # identical within group
        # corrupt one member per group (minority)
        adv = np.zeros(n, dtype=bool)
        adv[[0, 4, 8]] = True
        g = inject_plain(jnp.asarray(grads), jnp.asarray(adv), "rev_grad")
        out = repetition.majority_vote(code, g)
        np.testing.assert_allclose(np.asarray(out), honest.mean(axis=0), rtol=1e-6)

    def test_constant_attack(self, rng):
        n, r, d = 6, 3, 8
        code = repetition.build_repetition_code(n, r)
        honest = rng.randn(code.num_groups, d).astype(np.float32)
        grads = np.repeat(honest, r, axis=0)
        adv = np.zeros(n, dtype=bool)
        adv[[1, 5]] = True
        g = inject_plain(jnp.asarray(grads), jnp.asarray(adv), "constant")
        out = repetition.majority_vote(code, g)
        np.testing.assert_allclose(np.asarray(out), honest.mean(axis=0), rtol=1e-6)

    def test_rejects_bad_group_size(self):
        with pytest.raises(ValueError):
            repetition.build_repetition_code(7, 3)

    def test_vote_on_bfloat16_rows(self, rng):
        """The O(r·d) fingerprint vote bitcasts rows; cover the 2-byte-dtype
        path (bf16 lanes hand the vote bf16 gradients)."""
        n, r, d = 6, 3, 33
        code = repetition.build_repetition_code(n, r)
        honest = rng.randn(code.num_groups, d).astype(np.float32)
        grads = jnp.asarray(np.repeat(honest, r, axis=0)).astype(jnp.bfloat16)
        grads = grads.at[2].set(-grads[2])  # minority corruption in group 0
        out = repetition.majority_vote(code, grads)
        want = np.asarray(jnp.asarray(honest).astype(jnp.bfloat16)
                          .astype(jnp.float32)).mean(axis=0)
        np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), want,
                                   rtol=2e-2, atol=1e-2)

    def test_vote_tiebreak_is_lowest_index(self):
        """r=2 with one adversary ties the agreement counts; argmax must
        deterministically pick the lowest row index (documented tie-break)."""
        code = repetition.build_repetition_code(2, 2)
        rows = np.stack([np.full(5, 7.0), np.full(5, -7.0)]).astype(np.float32)
        out = repetition.majority_vote(code, jnp.asarray(rows))
        np.testing.assert_array_equal(np.asarray(out), rows[0])


class TestFingerprintCollisionResistance:
    """Adversarial collision properties of the vote fingerprints — attacks
    OUTSIDE the in-scope oblivious error modes (VERDICT r4 #10 / the r4
    advisor's constructed-collision finding)."""

    def _fps(self, rows, key=None):
        h1, h2, _ = repetition._row_fingerprints(jnp.asarray(rows), key=key)
        return np.asarray(h1), np.asarray(h2)

    def test_top_bit_pair_flip_does_not_collide(self, rng):
        """The killer attack on any LINEAR hash mod 2^32 (keyed or not):
        flipping the sign/top bit at two positions shifts the hash by
        2^31·(w_i + w_j) ≡ 0 whenever the weights have equal parity — a
        constructible, key-independent collision. The nonlinear avalanche
        must not exhibit it, at any position pair tried."""
        d = 64
        row = rng.randn(1, 1, d).astype(np.float32)
        bits = row.view(np.uint32)
        for (i, j) in [(0, 1), (3, 40), (62, 63), (17, 18)]:
            forged = bits.copy()
            forged[0, 0, i] ^= np.uint32(0x80000000)
            forged[0, 0, j] ^= np.uint32(0x80000000)
            both = np.concatenate([bits, forged], axis=1).view(np.float32)
            h1, h2 = self._fps(both)
            assert (h1[0, 0] != h1[0, 1]) or (h2[0, 0] != h2[0, 1])

    def test_position_swap_forgery_does_not_collide(self, rng):
        """The attack that killed the first salted construction (r5 review):
        with position entering by XOR next to the salt — mix(bits ^ pos ^ s)
        — setting forged[i] = honest[j] ^ pos[j] ^ pos[i] (and vice versa)
        swaps the (bits ^ pos) values between the two positions, the salt
        XORs out, and BOTH hashes collide for EVERY salt. The shipped
        construction (position added between two avalanche rounds) must not
        collide on this forgery, under the public salts and under keys."""
        import jax

        d = 48
        pos = (np.arange(d, dtype=np.uint64) * 2654435761) % (1 << 32)
        pos = pos.astype(np.uint32)
        row = rng.randn(1, 1, d).astype(np.float32)
        bits = row.view(np.uint32)
        for (i, j) in [(0, 1), (5, 33), (46, 47)]:
            forged = bits.copy()
            forged[0, 0, i] = bits[0, 0, j] ^ pos[j] ^ pos[i]
            forged[0, 0, j] = bits[0, 0, i] ^ pos[i] ^ pos[j]
            both = np.concatenate([bits, forged], axis=1).view(np.float32)
            for key in (None, jax.random.key(7)):
                h1, h2 = self._fps(both, key=key)
                assert (h1[0, 0] != h1[0, 1]) or (h2[0, 0] != h2[0, 1]), (
                    f"swap forgery at ({i},{j}) collided, key={key}"
                )

    def test_exact_mode_matches_fingerprint_on_attacks_and_defeats_swaps(
            self, rng):
        """vote_check='exact' must (a) agree with the fingerprint vote on
        honest + oblivious-attack inputs, and (b) reject ANY bitwise-distinct
        forgery by construction — including collision forgeries no hash can
        promise to stop (repetition.py threat-model tier 3)."""
        n, r, d = 6, 3, 24
        code = repetition.build_repetition_code(n, r)
        honest = rng.randn(2, d).astype(np.float32)
        grads = np.repeat(honest, r, axis=0)
        adv = np.zeros(n, dtype=bool)
        adv[[1, 5]] = True
        g = inject_plain(jnp.asarray(grads), jnp.asarray(adv), "rev_grad")
        out_fp = repetition.majority_vote(code, g)
        out_ex = repetition.majority_vote(code, g, method="exact")
        np.testing.assert_array_equal(np.asarray(out_fp), np.asarray(out_ex))
        # One-bit forgery in the LOWEST-index row of an otherwise-honest
        # group: the honest majority sits at rows 1-2, so the argmax
        # tie-break can't rescue a broken comparator — an eq-all-True bug
        # would elect the forged row 0 and fail this assertion.
        forged = grads.copy()
        fbits = forged[0].view(np.uint32)
        fbits[11] ^= np.uint32(1)
        out = repetition.majority_vote(code, jnp.asarray(forged),
                                       method="exact")
        # winners are bit-identical honest rows, so equality is exact; a
        # forged-row win would shift group 0's mean and fail bitwise
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(repetition.majority_vote(code, jnp.asarray(grads),
                                                method="exact")))
        with pytest.raises(ValueError, match="fingerprint.*exact|exact"):
            repetition.majority_vote(code, g, method="boyer")

    def test_vote_rejects_forged_row_under_keyed_fingerprints(self, rng):
        """End-to-end: a minority row forged by the top-bit pair-flip attack
        must still lose the vote when the step passes a PRNG key (the
        training-step configuration)."""
        import jax

        n, r, d = 3, 3, 32
        code = repetition.build_repetition_code(n, r)
        honest = rng.randn(1, d).astype(np.float32)
        grads = np.repeat(honest, r, axis=0)
        forged = grads[2].view(np.uint32).copy()
        forged[[5, 21]] ^= np.uint32(0x80000000)
        grads[2] = forged.view(np.float32)
        out = repetition.majority_vote(code, jnp.asarray(grads),
                                       key=jax.random.key(123))
        np.testing.assert_allclose(np.asarray(out), honest[0], rtol=1e-6)

    def test_fingerprint_vote_equals_exact_vote_randomized(self, rng):
        """Property check: over random group contents with crafted duplicate
        patterns (the full input domain of the vote), the fingerprint path
        and the exact path must elect bitwise-identical winners — the two
        methods differ only in collision surface, never in semantics."""
        import jax

        n, r, d = 8, 4, 17
        code = repetition.build_repetition_code(n, r)
        for trial in range(8):
            rows = rng.randn(n, d).astype(np.float32)
            # plant duplicate patterns: copy random rows over random rows
            # within each group so agreement counts take nontrivial values
            for g0 in range(code.num_groups):
                base = g0 * r
                for _ in range(rng.randint(0, 4)):
                    src, dst = rng.randint(0, r, size=2)
                    rows[base + dst] = rows[base + src]
            present = (rng.rand(n) > 0.2) if trial % 2 else None
            kw = dict(present=None if present is None
                      else jnp.asarray(present))
            out_fp = repetition.majority_vote(
                code, jnp.asarray(rows), key=jax.random.key(trial), **kw)
            out_ex = repetition.majority_vote(
                code, jnp.asarray(rows), method="exact", **kw)
            np.testing.assert_array_equal(
                np.asarray(out_fp), np.asarray(out_ex),
                err_msg=f"trial {trial} (present={present})")

    def test_vote_check_config_validation(self):
        from draco_tpu.config import TrainConfig

        with pytest.raises(ValueError, match="vote_check"):
            TrainConfig(approach="maj_vote", num_workers=9, group_size=3,
                        vote_check="sha256").validate()

    def test_key_changes_fingerprints_but_not_vote(self, rng):
        """Salts drawn from different keys must change the hash values
        (else the key isn't live) while the vote outcome — a function only
        of the equality pattern — stays identical."""
        import jax

        n, r, d = 6, 3, 16
        code = repetition.build_repetition_code(n, r)
        honest = rng.randn(2, d).astype(np.float32)
        grads = np.repeat(honest, r, axis=0)
        rows = jnp.asarray(grads).reshape(2, r, d)
        fp_a = self._fps(rows, key=jax.random.key(0))
        fp_b = self._fps(rows, key=jax.random.key(1))
        assert not np.array_equal(fp_a[0], fp_b[0])
        out_a = repetition.majority_vote(code, jnp.asarray(grads),
                                         key=jax.random.key(0))
        out_b = repetition.majority_vote(code, jnp.asarray(grads),
                                         key=jax.random.key(1))
        np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


def krum_oracle(grad_list, n, s):
    """Direct transcription of the reference loop semantics
    (baseline_master.py:278-291) as a float64 oracle."""
    score = []
    for i, g_i in enumerate(grad_list):
        dists = [np.linalg.norm(g_i - g_j) ** 2 for j, g_j in enumerate(grad_list) if i != j]
        score.append(sum(np.sort(dists)[: n - s - 2]))
    return grad_list[int(np.argmin(score))]


class TestAggregators:
    def test_mean(self, rng):
        g = rng.randn(8, 10).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(aggregation.mean(jnp.asarray(g))), g.mean(axis=0), rtol=1e-6
        )

    def test_krum_matches_oracle(self, rng):
        n, s, d = 8, 2, 30
        g = rng.randn(n, d).astype(np.float32)
        g[3] *= -100  # an attacked row
        out = aggregation.krum(jnp.asarray(g), s)
        want = krum_oracle(list(g), n, s)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)

    def test_krum_discards_adversary(self, rng):
        n, s, d = 10, 2, 16
        base = rng.randn(d).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, d).astype(np.float32)
        g[[2, 7]] = -100.0 * g[[2, 7]]
        out = np.asarray(aggregation.krum(jnp.asarray(g), s))
        assert np.linalg.norm(out - base) < 1.0

    def test_geometric_median_point_cloud(self, rng):
        # for a cloud with an extreme outlier, the geometric median stays
        # near the honest cluster while the mean does not
        n, d = 9, 12
        base = rng.randn(d).astype(np.float32)
        g = base[None, :] + 0.05 * rng.randn(n, d).astype(np.float32)
        g[4] = 1000.0
        gm = np.asarray(aggregation.geometric_median(jnp.asarray(g)))
        assert np.linalg.norm(gm - base) < 1.0
        assert np.linalg.norm(g.mean(axis=0) - base) > 50.0

    def test_geometric_median_weiszfeld_fixpoint(self, rng):
        # 1-D: geometric median == coordinate-wise median for odd count
        g = np.array([[1.0], [2.0], [3.0], [4.0], [100.0]], dtype=np.float32)
        gm = np.asarray(aggregation.geometric_median(jnp.asarray(g), iters=200))
        assert abs(gm[0] - 3.0) < 1e-2

    def test_aggregate_dispatch(self, rng):
        g = jnp.asarray(rng.randn(8, 5).astype(np.float32))
        for mode in aggregation.MODES:
            out = aggregation.aggregate(g, mode, s=1)
            assert out.shape == (5,)
        with pytest.raises(ValueError):
            aggregation.aggregate(g, "bogus")

    def test_coordinate_median_oracle(self, rng):
        g = rng.randn(9, 17).astype(np.float32)
        out = np.asarray(aggregation.coordinate_median(jnp.asarray(g)))
        np.testing.assert_allclose(out, np.median(g, axis=0), rtol=1e-6)

    def test_coordinate_median_present_stays_in_range(self, rng):
        g = rng.randn(9, 8).astype(np.float32)
        present = np.ones(9, bool)
        present[[1, 6]] = False
        g[[1, 6]] = 1e6  # absent rows hold garbage
        out = np.asarray(aggregation.coordinate_median(
            jnp.asarray(g), present=jnp.asarray(present)))
        kept = g[present]
        assert (out >= kept.min(axis=0) - 1e-6).all()
        assert (out <= kept.max(axis=0) + 1e-6).all()

    def test_trimmed_mean_oracle(self, rng):
        n, s = 9, 2
        g = rng.randn(n, 13).astype(np.float32)
        out = np.asarray(aggregation.trimmed_mean(jnp.asarray(g), s))
        want = np.sort(g, axis=0)[s:n - s].mean(axis=0)
        np.testing.assert_allclose(out, want, rtol=1e-6)
        with pytest.raises(ValueError):
            aggregation.trimmed_mean(jnp.asarray(g), 5)

    def test_trimmed_mean_kills_outliers(self, rng):
        base = rng.randn(12).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(9, 12).astype(np.float32)
        g[[0, 5]] = -100.0 * g[[0, 5]]
        out = np.asarray(aggregation.trimmed_mean(jnp.asarray(g), 2))
        assert np.linalg.norm(out - base) < 1.0

    def test_multi_krum_averages_honest_selection(self, rng):
        n, s = 10, 2
        base = rng.randn(16).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        g[[2, 7]] = -100.0 * g[[2, 7]]
        out = np.asarray(aggregation.multi_krum(jnp.asarray(g), s))
        assert np.linalg.norm(out - base) < 1.0
        # m honest rows averaged: closer to base than single-row krum noise
        one = np.asarray(aggregation.krum(jnp.asarray(g), s))
        honest = np.delete(g, [2, 7], axis=0)
        assert np.linalg.norm(out - honest.mean(axis=0)) \
            <= np.linalg.norm(one - honest.mean(axis=0)) + 1e-5

    def test_bulyan_discards_adversaries(self, rng):
        n, s = 11, 2  # n >= 4s+3
        base = rng.randn(16).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        g[[1, 8]] = -100.0 * g[[1, 8]]
        out = np.asarray(aggregation.bulyan(jnp.asarray(g), s))
        assert np.linalg.norm(out - base) < 1.0

    def test_multi_krum_present_still_excludes_adversary(self, rng):
        """Regression: with stragglers the kept count derives from the
        present count — n - s - 2 could select every present row and
        degenerate to a contaminated mean."""
        n, s = 10, 1
        base = rng.randn(16).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        g[4] = 1e4  # one Byzantine present row
        present = np.ones(n, bool)
        present[[0, 1, 2]] = False  # 3 stragglers: 7 present >= s+3
        out = np.asarray(aggregation.multi_krum(
            jnp.asarray(g), s, present=jnp.asarray(present)))
        assert np.linalg.norm(out - base) < 1.0

    def test_trimmed_mean_joint_straggler_adversary(self, rng):
        """Regression: the trim runs over present rows only, so absent-row
        garbage never votes and a Byzantine present row is still trimmed."""
        n, s = 9, 2
        base = rng.randn(16).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        g[[0, 5]] = -1e6  # Byzantine, count == s
        present = np.ones(n, bool)
        present[[1, 6]] = False  # absent rows hold garbage
        g[[1, 6]] = 777.0
        out = np.asarray(aggregation.trimmed_mean(
            jnp.asarray(g), s, present=jnp.asarray(present)))
        assert np.linalg.norm(out - base) < 1.0

    def test_coordinate_median_present_oracle(self, rng):
        g = rng.randn(9, 11).astype(np.float32)
        present = np.ones(9, bool)
        present[[2, 5, 8]] = False
        g[[2, 5, 8]] = 1e6
        out = np.asarray(aggregation.coordinate_median(
            jnp.asarray(g), present=jnp.asarray(present)))
        np.testing.assert_allclose(out, np.median(g[present], axis=0),
                                   rtol=1e-6)

    def test_bulyan_present_mask(self, rng):
        n, s = 11, 2
        base = rng.randn(16).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        g[3] = -100.0 * g[3]
        present = np.ones(n, bool)
        present[9] = False
        g[9] = 1e6
        out = np.asarray(aggregation.bulyan(
            jnp.asarray(g), s, present=jnp.asarray(present)))
        assert np.linalg.norm(out - base) < 1.0

    def test_bulyan_many_stragglers_still_filters(self, rng):
        """Regression: θ/β derive from the present count — with 4 of 11 rows
        absent, the Krum stage must still exclude the Byzantine present row
        rather than degenerate to a plain present-mean."""
        n, s = 11, 1
        base = rng.randn(16).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        g[4] = 1e4  # Byzantine present row
        present = np.ones(n, bool)
        present[[0, 1, 2, 3]] = False
        g[[0, 1, 2, 3]] = 555.0
        out = np.asarray(aggregation.bulyan(
            jnp.asarray(g), s, present=jnp.asarray(present)))
        assert np.linalg.norm(out - base) < 1.0

    def test_median_rules_reject_over_straggled_config(self):
        from draco_tpu.config import TrainConfig

        with pytest.raises(ValueError, match="> 2 \\* worker_fail"):
            TrainConfig(approach="baseline", mode="trimmed_mean",
                        num_workers=9, worker_fail=2, straggle_mode="drop",
                        straggle_count=6).validate()

    def test_trimmed_mean_present_only_oracle(self, rng):
        """With a present mask the trim is exactly the numpy trimmed mean of
        the present rows — no fill values enter the kept middle (advisor r2:
        a median fill lands e copies inside the middle and biases the mean
        toward the median as straggle_count grows)."""
        n, s = 9, 2
        g = rng.randn(n, 13).astype(np.float32)
        present = np.ones(n, bool)
        present[[1, 6]] = False
        g[[1, 6]] = 1e6  # absent-row garbage must not vote
        out = np.asarray(aggregation.trimmed_mean(
            jnp.asarray(g), s, present=jnp.asarray(present)))
        kept = np.sort(g[present], axis=0)[s:present.sum() - s]
        np.testing.assert_allclose(out, kept.mean(axis=0), rtol=1e-6)

    def test_bulyan_warns_below_guarantee_threshold(self, rng):
        """n < 4s+3 runs but warns that the Byzantine guarantee is degraded
        (advisor r2: silent beta clamp)."""
        g = rng.randn(7, 8).astype(np.float32)
        with pytest.warns(UserWarning, match="4s\\+3"):
            aggregation.bulyan(jnp.asarray(g), 2)

    def test_excluded_nonfinite_rows_cannot_poison(self, rng):
        """A non-finite excluded row (overflowed Byzantine present row, or
        NaN garbage in an absent row) must not leak into trimmed_mean /
        bulyan / the aggregate() dispatch via 0·inf = NaN products
        (code-review r3)."""
        n, s = 9, 2
        base = rng.randn(16).astype(np.float32)
        g0 = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        present = np.ones(n, bool)
        present[6] = False

        # absent-row NaN garbage: every rule must stay finite (aggregate()
        # zeroes absent rows before dispatch)
        g = g0.copy()
        g[6] = np.nan
        for mode in ("normal", "geometric_median", "krum", "coord_median",
                     "trimmed_mean", "multi_krum", "bulyan"):
            out = np.asarray(aggregation.aggregate(
                jnp.asarray(g), mode, s=s, present=jnp.asarray(present)))
            assert np.isfinite(out).all(), f"{mode} poisoned by absent NaN"

        # non-finite Byzantine PRESENT row: the rank/selection rules exclude
        # it by weight and must not let 0·inf products reintroduce it
        # (mean is legitimately inf there; Weiszfeld-on-inf matches the
        # reference's hdmedians behaviour — neither is asserted)
        g = g0.copy()
        g[6] = np.nan
        g[0] = np.inf
        for mode in ("krum", "coord_median", "trimmed_mean", "multi_krum",
                     "bulyan"):
            out = np.asarray(aggregation.aggregate(
                jnp.asarray(g), mode, s=s, present=jnp.asarray(present)))
            assert np.isfinite(out).all(), f"{mode} poisoned by present inf"
        out = np.asarray(aggregation.trimmed_mean(
            jnp.asarray(g), s, present=jnp.asarray(present)))
        assert np.linalg.norm(out - base) < 1.0

    def test_bulyan_no_warning_at_full_guarantee(self, rng):
        import warnings as _w

        g = rng.randn(11, 8).astype(np.float32)
        with _w.catch_warnings():
            _w.simplefilter("error")
            aggregation.bulyan(jnp.asarray(g), 2)


class TestAttacks:
    def test_plain_modes(self, rng):
        from draco_tpu import attacks

        g = jnp.asarray(rng.randn(4, 6).astype(np.float32))
        mask = jnp.asarray(np.array([True, False, False, True]))
        out = np.asarray(attacks.inject_plain(g, mask, "rev_grad"))
        np.testing.assert_allclose(out[0], -100 * np.asarray(g)[0], rtol=1e-6)
        np.testing.assert_allclose(out[1], np.asarray(g)[1], rtol=1e-6)
        out = np.asarray(attacks.inject_plain(g, mask, "constant"))
        np.testing.assert_allclose(out[3], -100.0)
        # the random attack is REAL now (ISSUE 14 satellite — the
        # reference left it a passthrough TODO): a seeded N(0,1) payload
        # scaled by the magnitude, drawn from the (seed, step) schedule
        # discipline — deterministic, worker rows independent, honest
        # rows untouched
        out = np.asarray(attacks.inject_plain(g, mask, "random",
                                              step=3, seed=428))
        np.testing.assert_allclose(out[1], np.asarray(g)[1], rtol=1e-6)
        np.testing.assert_allclose(out[2], np.asarray(g)[2], rtol=1e-6)
        assert not np.allclose(out[0], np.asarray(g)[0])
        assert not np.allclose(out[0], out[3])  # per-row independent draws
        assert np.abs(out[0]).max() > 10  # magnitude-scaled, not a nudge
        again = np.asarray(attacks.inject_plain(g, mask, "random",
                                                step=3, seed=428))
        np.testing.assert_array_equal(out, again)  # same (seed, step) draw
        other = np.asarray(attacks.inject_plain(g, mask, "random",
                                                step=4, seed=428))
        assert not np.array_equal(out, other)  # distinct per step
        # a keyless call has no stream to draw from — named config error
        with pytest.raises(ValueError, match="random"):
            attacks.attack_plain(g, "random")
        # cyclic wire form: additive on the encoded rows, seeded the same
        re_ = jnp.asarray(np.asarray(g)[:3])
        o_re, o_im = attacks.inject_cyclic(re_, re_, jnp.asarray(
            np.array([False, True, False])), "random", step=3, seed=428)
        np.testing.assert_allclose(np.asarray(o_re)[0], np.asarray(re_)[0])
        assert not np.allclose(np.asarray(o_re)[1], np.asarray(re_)[1])
        # independent re/im draws
        assert not np.allclose(np.asarray(o_re)[1] - np.asarray(re_)[1],
                               np.asarray(o_im)[1] - np.asarray(re_)[1])

    def test_cyclic_additive(self, rng):
        from draco_tpu import attacks

        re = jnp.asarray(rng.randn(3, 4).astype(np.float32))
        im = jnp.asarray(rng.randn(3, 4).astype(np.float32))
        mask = jnp.asarray(np.array([False, True, False]))
        o_re, o_im = attacks.inject_cyclic(re, im, mask, "rev_grad")
        np.testing.assert_allclose(np.asarray(o_re)[1], -99 * np.asarray(re)[1], rtol=1e-5)
        o_re, o_im = attacks.inject_cyclic(re, im, mask, "constant")
        np.testing.assert_allclose(np.asarray(o_re)[1], np.asarray(re)[1] - 100.0, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(o_im)[1], np.asarray(im)[1], rtol=1e-6)


class TestColludingAttacks:
    """alie / ipm (beyond-reference): omniscient colluders computing their
    payload from honest-row statistics."""

    def test_ipm_payload_and_honest_rows(self, rng):
        from draco_tpu import attacks

        g = jnp.asarray(rng.randn(8, 16).astype(np.float32))
        mask = jnp.asarray(np.arange(8) == 3)
        out = np.asarray(attacks.inject_plain(g, mask, "ipm", n_mal=1))
        honest = np.asarray(g)[np.arange(8) != 3]
        np.testing.assert_allclose(out[3], -0.5 * honest.mean(0), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(out[np.arange(8) != 3], honest, rtol=1e-6)

    def test_alie_payload_hides_in_variance(self, rng):
        from draco_tpu import attacks
        from draco_tpu.attacks import _alie_z

        g = jnp.asarray(rng.randn(8, 16).astype(np.float32))
        mask = jnp.asarray(np.arange(8) < 3)  # z(8,3)=0.253 > 0: a REAL payload
        out = np.asarray(attacks.inject_plain(g, mask, "alie", n_mal=3))
        honest = np.asarray(g)[3:]
        mu, sigma = honest.mean(0), honest.std(0)
        z = _alie_z(8, 3)
        assert z > 0, "test premise: quantile must be positive at (8, 3)"
        np.testing.assert_allclose(out[0], mu - z * sigma, rtol=1e-4,
                                   atol=1e-5)
        # the payload stays inside the honest spread (that is the attack)
        assert np.all(np.abs(out[0] - mu) <= 3.1 * sigma + 1e-6)

    def test_alie_warns_when_inert(self, rng):
        import warnings

        from draco_tpu import attacks

        g = jnp.asarray(rng.randn(8, 4).astype(np.float32))
        mask = jnp.asarray(np.arange(8) == 0)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            attacks.inject_plain(g, mask, "alie", n_mal=1)  # z(8,1) < 0
        assert any("inert" in str(w.message) for w in caught)

    def test_sign_of_magnitude_cannot_invert_payload(self, rng):
        """A positive --adversarial must not flip alie/ipm direction (the
        knob's sign encodes direction only for rev_grad's multiplicative
        payload) — regression for the r3 advisor finding."""
        from draco_tpu import attacks

        g = jnp.asarray(rng.randn(8, 16).astype(np.float32))
        mask = jnp.asarray(np.arange(8) < 3)
        for mode in ("alie", "ipm"):
            neg = np.asarray(attacks.inject_plain(g, mask, mode,
                                                  magnitude=-100.0, n_mal=3))
            pos = np.asarray(attacks.inject_plain(g, mask, mode,
                                                  magnitude=100.0, n_mal=3))
            np.testing.assert_array_equal(pos, neg)

    def test_ipm_poisons_mean_but_not_coord_median(self, rng):
        from draco_tpu import attacks

        # tight honest cluster so the robust rule has signal
        g = jnp.asarray((rng.randn(8, 32) * 0.01 + 1.0).astype(np.float32))
        mask = jnp.asarray(np.arange(8) < 2)
        out = attacks.inject_plain(g, mask, "ipm", n_mal=2)
        honest_mean = np.asarray(g)[2:].mean(0)
        mean_agg = np.asarray(jnp.mean(out, axis=0))
        med_agg = np.asarray(aggregation.coordinate_median(out))
        # mean dragged toward -0.5*mu by the colluders; median stays put
        assert np.abs(mean_agg - honest_mean).max() > 0.3
        assert np.abs(med_agg - honest_mean).max() < 0.05

    def test_jit_static_quantile(self, rng):
        """n_mal is static config, so alie traces under jit."""
        import jax

        from draco_tpu import attacks

        g = jnp.asarray(rng.randn(8, 8).astype(np.float32))
        mask = jnp.asarray(np.arange(8) == 1)
        f = jax.jit(lambda g, m: attacks.inject_plain(g, m, "alie", n_mal=1))
        out = np.asarray(f(g, mask))
        assert np.isfinite(out).all()

    def test_cyclic_rejects_colluding_modes(self):
        from draco_tpu.config import TrainConfig

        with pytest.raises(ValueError, match="decode is exact"):
            TrainConfig(network="LeNet", dataset="synthetic-mnist",
                        approach="cyclic", num_workers=8, worker_fail=1,
                        err_mode="ipm", batch_size=4).validate()

    def test_mean_under_ipm_trains_worse_than_median(self):
        """End-to-end under a strong ipm (magnitude 8x the canonical eps,
        2/8 colluders): the mean update's direction REVERSES
        ((6*mu - 8*mu)/8 = -0.25*mu) so the undefended run must stall or
        diverge, while coord-median discards the colluders and learns."""
        from draco_tpu.config import TrainConfig
        from draco_tpu.data.datasets import load_dataset
        from draco_tpu.runtime import make_mesh
        from draco_tpu.training.trainer import Trainer

        losses = {}
        ds = load_dataset("synthetic-mnist")
        for mode in ("normal", "coord_median"):
            cfg = TrainConfig(
                network="FC", dataset="synthetic-mnist", batch_size=16,
                lr=0.05, num_workers=8, approach="baseline", mode=mode,
                worker_fail=2, err_mode="ipm", adversarial=-800.0,
                max_steps=30, eval_freq=0, train_dir="", log_every=1000,
            )
            tr = Trainer(cfg, mesh=make_mesh(8), dataset=ds, quiet=True)
            last = tr.run()
            losses[mode] = float(last["loss"])
            tr.close()
        # the attack must visibly bite the mean AND median must beat it
        assert losses["coord_median"] < 2.0, losses
        assert losses["normal"] > losses["coord_median"] + 0.2, losses


class TestSchedules:
    def test_adversary_schedule_deterministic(self):
        from draco_tpu import rng as drng

        a = drng.adversary_schedule(428, 50, 8, 2)
        b = drng.adversary_schedule(428, 50, 8, 2)
        np.testing.assert_array_equal(a, b)
        assert (a.sum(axis=1) == 2).all()

    def test_group_seeds_agree(self):
        from draco_tpu import rng as drng

        np.testing.assert_array_equal(drng.group_seeds(428, 4), drng.group_seeds(428, 4))

    def test_epoch_permutation(self):
        from draco_tpu import rng as drng

        p1 = drng.epoch_permutation(5, 0, 100)
        p2 = drng.epoch_permutation(5, 1, 100)
        assert not np.array_equal(p1, p2)
        assert sorted(p1) == list(range(100))


class TestKrumPenaltyBounded:
    def test_many_absent_rows_do_not_overflow(self, rng):
        """Regression: a finfo.max-scale absent penalty overflowed the score
        sum to inf once >= 4 absent entries landed in a row's k nearest slots,
        degenerating argmin to index 0."""
        n, s = 10, 1
        base = rng.randn(16).astype(np.float32)
        g = base[None, :] + 0.01 * rng.randn(n, 16).astype(np.float32)
        g[0] = 1e6  # index 0 is an outlier — the degenerate argmin would pick it
        present = np.ones(n, dtype=bool)
        present[[1, 2, 3, 4, 5]] = False  # 5 absent > s+1, permitted by baseline
        out = np.asarray(aggregation.krum(jnp.asarray(g), s,
                                          present=jnp.asarray(present)))
        assert np.all(np.isfinite(out))
        assert not np.allclose(out, g[0])
        assert any(np.allclose(out, g[i]) for i in range(6, n))

    def test_still_matches_oracle_after_penalty_change(self, rng):
        n, s, d = 8, 2, 30
        g = rng.randn(n, d).astype(np.float32)
        g[5] *= 77.0
        out = aggregation.krum(jnp.asarray(g), s)
        want = krum_oracle(list(g), n, s)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


class TestWeiszfeldIterationBudget:
    """Justifies config.geomedian_iters=80 (the bench's vs_baseline divides
    by geo-median cost, which is linear in this knob): on representative
    gradient stacks — honest cluster + reference-style -100x attacked rows —
    80 float32 Weiszfeld iterations match a float64 run iterated to
    convergence (the hdmedians stand-in) to float32 resolution."""

    @staticmethod
    def _converged_f64(g, tol=1e-14, cap=20000):
        y = g.mean(axis=0)
        for _ in range(cap):
            dist = np.linalg.norm(g - y[None, :], axis=1)
            w = 1.0 / np.maximum(dist, 1e-300)
            y_new = (w @ g) / w.sum()
            if np.linalg.norm(y_new - y) <= tol * max(np.linalg.norm(y), 1e-30):
                return y_new
            y = y_new
        return y

    @pytest.mark.parametrize("n,d,n_adv", [(8, 1000, 0), (8, 1000, 2),
                                           (16, 5000, 3), (32, 2000, 5)])
    def test_80_iters_matches_converged_float64(self, n, d, n_adv, rng):
        base = rng.randn(d).astype(np.float32) * 0.1
        g = base[None, :] + 0.02 * rng.randn(n, d).astype(np.float32)
        if n_adv:
            g[:n_adv] = -100.0 * g[:n_adv]  # reference rev_grad magnitude
        want = self._converged_f64(g.astype(np.float64))
        got = np.asarray(aggregation.geometric_median(jnp.asarray(g), iters=80))
        scale = max(np.linalg.norm(want), 1e-30)
        rel = np.linalg.norm(got - want) / scale
        assert rel < 5e-5, f"rel err {rel:.2e} after 80 iters"

    def test_40_iters_would_not_suffice_under_attack(self, rng):
        """The knob is not slack: fewer iterations measurably lag the
        converged point on the attacked stacks the bench times."""
        n, d = 8, 1000
        base = rng.randn(d).astype(np.float32) * 0.1
        g = base[None, :] + 0.02 * rng.randn(n, d).astype(np.float32)
        g[:2] = -100.0 * g[:2]
        want = self._converged_f64(g.astype(np.float64))
        scale = max(np.linalg.norm(want), 1e-30)
        rel = lambda it: np.linalg.norm(
            np.asarray(aggregation.geometric_median(jnp.asarray(g), iters=it)) - want
        ) / scale
        assert rel(80) < rel(10) or rel(10) < 5e-5

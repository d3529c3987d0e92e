"""Work counts from shapes match hand counts at a tiny size."""
import work


def test_sweep_counts_by_hand():
    # 3 ratings, K=2: statistics 2 sides x 3 x 2*2^2 = 48; solves for
    # 2 rated users + 2 rated items, each 2^3/3 + 2*2^2 = 8/3 + 8
    assert work.sweep_stats_flops(3, 2) == 48
    assert abs(work.sweep_solve_flops(4, 2) - 4 * (8 / 3 + 8)) < 1e-12
    assert abs(work.sweep_flops(3, 2, 2, 2) - (48 + 4 * (8 / 3 + 8))) < 1e-12
    assert work.sweep_updates(3) == 6


def test_topn_counts_by_hand():
    # B=2 users, S*K=4, N=5 items: 2*2*4*5 FLOPs; V' (5x4) and rows (2x4) f32
    assert work.topn_flops(2, 4, 5) == 80
    assert work.topn_bytes(2, 4, 5) == 4 * 4 * 7


def test_foldin_counts_by_hand():
    # S=2 draws, 3 ratings over 1 user, K=2
    assert abs(work.foldin_flops(3, 1, 2, 2) - 2 * (3 * 8 + (8 / 3 + 8))) < 1e-12

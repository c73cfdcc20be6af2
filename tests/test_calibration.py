"""EvaluationCalibration tests — bucketed counts vs hand-computed values
(reference eval/EvaluationCalibration.java)."""

import numpy as np

from deeplearning4j_tpu.eval import EvaluationCalibration


def _tiny():
    # 4 examples, 2 classes; probabilities chosen to land in known bins
    labels = np.array([[1, 0],
                       [0, 1],
                       [1, 0],
                       [0, 1]], np.float32)
    preds = np.array([[0.95, 0.05],
                      [0.30, 0.70],
                      [0.45, 0.55],
                      [0.10, 0.90]], np.float32)
    return labels, preds


class TestReliability:
    def test_bucketed_counts_hand_computed(self):
        ec = EvaluationCalibration(reliability_num_bins=10,
                                   histogram_num_bins=10)
        labels, preds = _tiny()
        ec.eval(labels, preds)
        # class 0 probabilities: 0.95->bin9, 0.30->bin3, 0.45->bin4, 0.10->bin1
        tc0 = ec.rdiag_total_count[:, 0]
        assert tc0[9] == 1 and tc0[3] == 1 and tc0[4] == 1 and tc0[1] == 1
        assert tc0.sum() == 4
        # positives for class 0 land in bins 9 (0.95, label 1) and 4 (0.45, label 1)
        pc0 = ec.rdiag_pos_count[:, 0]
        assert pc0[9] == 1 and pc0[4] == 1 and pc0.sum() == 2
        # sum of predictions in bin 9 for class 0 is exactly 0.95
        np.testing.assert_allclose(ec.rdiag_sum_predictions[9, 0], 0.95)

    def test_reliability_diagram_values(self):
        ec = EvaluationCalibration(reliability_num_bins=2)
        labels = np.array([[1, 0], [0, 1], [1, 0], [0, 1]], np.float32)
        preds = np.array([[0.8, 0.2], [0.3, 0.7], [0.6, 0.4], [0.4, 0.6]],
                         np.float32)
        ec.eval(labels, preds)
        rd = ec.get_reliability_diagram(0)
        # class 0: lower bin [0,0.5): p=0.3 (label 0), p=0.4 (label 0)
        #          upper bin [0.5,1]: p=0.8 (label 1), p=0.6 (label 1)
        np.testing.assert_allclose(rd.mean_predicted_value, [0.35, 0.7])
        np.testing.assert_allclose(rd.fraction_positives, [0.0, 1.0])

    def test_p_equal_one_lands_in_last_bin(self):
        ec = EvaluationCalibration(reliability_num_bins=10)
        labels = np.array([[1.0, 0.0]], np.float32)
        preds = np.array([[1.0, 0.0]], np.float32)
        ec.eval(labels, preds)
        assert ec.rdiag_total_count[9, 0] == 1     # p == 1.0 edge case
        assert ec.rdiag_total_count[0, 1] == 1     # p == 0.0 → first bin


class TestHistograms:
    def test_label_and_prediction_counts(self):
        ec = EvaluationCalibration()
        labels, preds = _tiny()
        ec.eval(labels, preds)
        np.testing.assert_array_equal(ec.get_label_counts_each_class(), [2, 2])
        # argmax predictions: c0, c1, c1, c1
        np.testing.assert_array_equal(ec.get_prediction_counts_each_class(),
                                      [1, 3])

    def test_residual_histogram_hand_computed(self):
        ec = EvaluationCalibration(histogram_num_bins=10)
        labels = np.array([[1, 0]], np.float32)
        preds = np.array([[0.72, 0.28]], np.float32)
        ec.eval(labels, preds)
        # residuals: |1-0.72| = 0.28 -> bin 2 ; |0-0.28| = 0.28 -> bin 2
        h = ec.get_residual_plot_all_classes()
        assert h.bin_counts[2] == 2 and h.bin_counts.sum() == 2
        # per class: only label class 0 contributes, its residual 0.28
        h0 = ec.get_residual_plot(0)
        assert h0.bin_counts[2] == 1 and h0.bin_counts.sum() == 1
        h1 = ec.get_residual_plot(1)
        assert h1.bin_counts.sum() == 0

    def test_probability_histogram_per_class(self):
        ec = EvaluationCalibration(histogram_num_bins=4)
        labels, preds = _tiny()
        ec.eval(labels, preds)
        # label class 1 rows have P(class1) = 0.70 (bin 2), 0.90 (bin 3)
        h1 = ec.get_probability_histogram(1)
        assert h1.bin_counts[2] == 1 and h1.bin_counts[3] == 1
        assert h1.bin_counts.sum() == 2


class TestMaskingAndTimeSeries:
    def test_per_example_mask_excludes_rows(self):
        ec = EvaluationCalibration()
        labels, preds = _tiny()
        mask = np.array([1, 1, 0, 0], np.float32)
        ec.eval(labels, preds, mask)
        assert ec.rdiag_total_count[:, 0].sum() == 2
        np.testing.assert_array_equal(ec.get_label_counts_each_class(), [1, 1])
        np.testing.assert_array_equal(ec.get_prediction_counts_each_class(),
                                      [1, 1])

    def test_time_series_flattening_matches_2d(self):
        ec3 = EvaluationCalibration()
        labels, preds = _tiny()
        l3 = labels.reshape(2, 2, 2)
        p3 = preds.reshape(2, 2, 2)
        ec3.eval(l3, p3, np.ones((2, 2), np.float32))
        ec2 = EvaluationCalibration()
        ec2.eval(labels, preds)
        np.testing.assert_array_equal(ec3.rdiag_total_count,
                                      ec2.rdiag_total_count)
        np.testing.assert_array_equal(ec3.prob_overall, ec2.prob_overall)


class TestMergeAndECE:
    def test_merge_equals_joint_eval(self):
        labels, preds = _tiny()
        a = EvaluationCalibration().eval(labels[:2], preds[:2])
        b = EvaluationCalibration().eval(labels[2:], preds[2:])
        a.merge(b)
        joint = EvaluationCalibration().eval(labels, preds)
        np.testing.assert_array_equal(a.rdiag_total_count,
                                      joint.rdiag_total_count)
        np.testing.assert_array_equal(a.rdiag_pos_count, joint.rdiag_pos_count)
        np.testing.assert_allclose(a.rdiag_sum_predictions,
                                   joint.rdiag_sum_predictions)

    def test_ece_perfect_calibration_is_zero(self):
        ec = EvaluationCalibration(reliability_num_bins=1)
        # one bin: conf mean = 0.5, accuracy = 0.5 → ECE 0
        labels = np.array([[1, 0], [0, 1]], np.float32)
        preds = np.array([[0.5, 0.5], [0.5, 0.5]], np.float32)
        ec.eval(labels, preds)
        assert abs(ec.expected_calibration_error()) < 1e-12
        assert "ECE" in ec.stats()


def test_masked_column_cannot_win_argmax():
    """A masked-out class column must not be counted as the predicted class
    even when its raw probability is the max (per-output mask)."""
    ec = EvaluationCalibration()
    labels = np.array([[0, 1, 0]], np.float32)
    preds = np.array([[0.1, 0.3, 0.6]], np.float32)   # class 2 wins raw argmax
    mask = np.array([[1, 1, 0]], np.float32)          # ...but is masked out
    ec.eval(labels, preds, mask=mask)
    assert ec.prediction_counts[2] == 0
    assert ec.prediction_counts[1] == 1


def test_masked_label_column_excluded_from_per_class_stats():
    """Rows whose true-label column is masked out must not contribute to
    that class's residual/probability histograms."""
    ec = EvaluationCalibration()
    labels = np.array([[0, 1, 0]], np.float32)
    preds = np.array([[0.1, 0.3, 0.6]], np.float32)
    mask = np.array([[1, 0, 1]], np.float32)          # true class 1 masked
    ec.eval(labels, preds, mask=mask)
    assert ec.residual_by_class[:, 1].sum() == 0
    assert ec.prob_by_class[:, 1].sum() == 0
